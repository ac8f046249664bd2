"""Device time of the collectives over device busy time, per chip, in the
traced segment.

An operation counts as a collective when its kind (``trace.Op.kind``: the
name before its number) is one of the HLO collectives, ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``all-to-all`` and
``collective-permute``, or one of their ``-start`` / ``-done`` halves.  On
a TPU trace an operation that a JAX collective emits may instead be named
after it (a ``shard_map`` ``psum`` reads ``psum``), so those names count
too.  ``None`` where the trace holds no collective: a one-chip cell."""

HLO = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
       "collective-permute")
JAX = ("psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
       "psum_scatter", "reduce_scatter")
KINDS = frozenset(k + half for k in HLO + JAX for half in ("", "-start",
                                                           "-done"))


def read(obs):
    t = obs.trace
    if t is None or t.busy_s <= 0:
        return None
    s = sum(sec for kind, sec in t.top_ops if kind in KINDS)
    if s <= 0:
        return None
    return 100.0 * s / t.busy_s

"""The system under test: the repro serving stack, stood up for one cell.

Weights come from the cell's reference module (the same seeded bfloat16
values the reference reads), are put into the program's parameter tree
by the family's adapter (``bench/adapters/<family>.py``), in the layout
the configuration serves, in one jitted call on the device, and go
through ``repro.serving.prepare``.  Where the configuration states a
mesh (``program.mesh``, ``[data, model]``), the tree is built already
placed as ``prepare`` places it, so no chip ever holds the whole model.
``Engine.run`` is the only entry the window drives.
"""

from __future__ import annotations

from typing import List, Sequence

import jax


def serving_spec(cfg: dict, mix: dict, *, control: str = None,
                 backend: str = "auto"):
    from repro.serving import ServingSpec

    serve = cfg["program"]
    sparsity = serve.get("sparsity")
    mesh = serve.get("mesh")
    eng = mix["engine"]
    return ServingSpec(
        layout=serve["layout"],
        sparsity=None if sparsity is None else tuple(sparsity),
        qdtype=control if control is not None else serve.get("qdtype"),
        kv_qdtype=serve.get("kv_qdtype"), backend=backend,
        mesh=None if mesh is None else tuple(mesh),
        slots=eng["slots"], max_len=eng["max_len"],
        block_len=eng["block_len"], prefill_chunk=eng["prefill_chunk"])


def placement(spec, model_cfg, tree):
    """The shardings ``prepare`` gives ``tree`` (arrays or their shapes)
    on ``spec.mesh``: the program's own rules on the mesh it builds."""
    from repro.launch.mesh import make_axis_env, make_mesh
    from repro.launch.shardings import ShardingRules

    mesh = make_mesh(spec.mesh, ("data", "model"))
    return ShardingRules(make_axis_env(mesh), model_cfg).tree_shardings(tree)


def to_requests(drawn: Sequence) -> List:
    from repro.serving import Request

    return [Request(rid=r.rid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens, arrival=r.arrival)
            for r in drawn]


class Served:
    """The prepared model and its engine for one run."""

    def __init__(self, ref, adapter, seed: int, cfg: dict, mix: dict, *,
                 control: str = None, backend: str = "auto"):
        from repro import serving

        self.spec = serving_spec(cfg, mix, control=control, backend=backend)
        self.model_cfg = self.spec.apply_to(adapter.model_config(cfg))
        shardings = None
        if self.spec.mesh is not None:
            shardings = placement(self.spec, self.model_cfg, jax.eval_shape(
                lambda: adapter.program_params(ref, seed, cfg)))
        params = adapter.program_params(ref, seed, cfg, shardings)
        self.prepared = serving.prepare(params, self.spec, cfg=self.model_cfg)
        del params
        jax.block_until_ready(self.prepared.params)
        self.engine = serving.Engine(self.prepared)

    def weight_bytes(self) -> int:
        return sum(x.nbytes for x in jax.tree.leaves(self.prepared.params))

    def run(self, drawn: Sequence):
        return self.engine.run(to_requests(drawn))

"""The benchmark's counting code, on the CPU at smoke sizes: byte and
FLOP functions against the leaves ``prepare`` makes, the float32
reference against the program's jnp tier, the peaks table, and the
seeded traffic draw."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arrivals, counting, sut
from bench.harness import HERE, _load_module
from test_bench_cells import MEDIUM

ROOT = Path(__file__).resolve().parents[2]
ref = _load_module(HERE / "reference" / "internlm2.py", "bench_ref_test")
adapter = _load_module(HERE / "adapters" / "internlm2.py",
                       "bench_adapter_test")


def smoke_config(layout="compressed", sparsity=(2, 4), qdtype=None):
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "internlm2-1_8b-2of4.json").read_text())
    cfg.update(name="smoke", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=256)
    cfg["program"] = dict(cfg["program"], layout=layout,
                          sparsity=None if sparsity is None
                          else list(sparsity), qdtype=qdtype)
    return cfg


def smoke_mix():
    mix = json.loads((ROOT / "bench" / "traffic" / "chat_short.json")
                     .read_text())
    mix["engine"] = {"slots": 2, "max_len": 32, "block_len": 8,
                     "prefill_chunk": 8}
    return mix


def prepared(cfg, seed=7):
    from repro import serving

    spec = sut.serving_spec(cfg, smoke_mix(), backend="jnp")
    mc = spec.apply_to(adapter.model_config(cfg))
    params = adapter.program_params(ref, seed, cfg)
    return serving.prepare(params, spec, cfg=mc), mc


@pytest.mark.parametrize("layout,sparsity,qdtype", [
    ("dense", None, None), ("compressed", (2, 4), None),
    ("dense", None, "int8"), ("compressed", (2, 4), "int8")])
def test_stored_bytes_match_prepared_leaves(layout, sparsity, qdtype):
    cfg = smoke_config(layout, sparsity, qdtype)
    prep, _ = prepared(cfg)
    slot = prep.params["stages"][0]["slot0"]
    layers = cfg["num_hidden_layers"]
    for name, (k, o) in ref.linear_shapes(cfg).items():
        group, pname = adapter.PROGRAM_NAMES[name]
        leaf = slot[group][pname]
        got = sum(x.nbytes for x in jax.tree.leaves(leaf))
        want = layers * counting.stored_weight_bytes(
            k, o, layout, sparsity, qdtype)
        assert got == want, (name, got, want)


def test_kernel_call_counts():
    # a 2:4 call does half the multiply-adds and reads half the values
    f_d, b_d = counting.kernel_call(2048, 8192, 16, "dense")
    f_s, b_s = counting.kernel_call(2048, 8192, 16, "compressed", (2, 4))
    assert f_d == 2 * 16 * 2048 * 8192 and f_s == f_d / 2
    acts = 16 * 2048 * 2 + 16 * 8192 * 2
    assert b_d == 2048 * 8192 * 2 + acts
    assert b_s == 1024 * 8192 * 2 + 256 * 8192 + acts
    # a fused gate-up contracts two weights against one activation tile
    f_2, b_2 = counting.kernel_call(2048, 8192, 16, "dense", weights=2)
    assert f_2 == 2 * f_d and b_2 == 2 * 2048 * 8192 * 2 + acts


def test_request_flops_counts_each_position():
    cfg = smoke_config("dense", None)
    z = ref.dims(cfg)
    shapes = ref.linear_shapes(cfg)
    args = (shapes, z["layers"], z["heads"], z["head_dim"], z["d"],
            z["vocab"])
    one = counting.request_flops(3, 2, *args)
    # 3 prompt tokens and 1 fed-back output token, each through every
    # linear and attending over its own position + 1 keys; 2 sampled
    lin = sum(2 * k * o for k, o in shapes.values())
    by_token = sum(z["layers"] * (lin + 4 * z["heads"] * z["head_dim"]
                                  * (p + 1)) for p in range(4))
    assert one == pytest.approx(by_token + 2 * 2 * z["d"] * z["vocab"])
    half = counting.request_flops(3, 2, *args, sparsity=(2, 4))
    assert half < one


def test_peaks_reject_unknown_device_kind():
    assert counting.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        counting.peaks("TPU v99")
    peak = counting.peaks("TPU v5 lite")
    assert counting.least_time(197e12, 1.0, peak) == pytest.approx(1.0)
    assert counting.least_time(1.0, 819e9, peak) == pytest.approx(1.0)


@pytest.mark.parametrize("layout,sparsity", [("dense", None),
                                             ("compressed", (2, 4))])
def test_reference_matches_program_jnp_tier(layout, sparsity):
    """The float32 reference against the program's own jnp forward on the
    same weights, both in float32: they must agree to rounding."""
    import dataclasses

    from repro.kernels.dispatch import use_dispatch
    from repro.models import forward

    cfg = smoke_config(layout, sparsity)
    prep, mc = prepared(cfg, seed=11)
    mc32 = dataclasses.replace(mc, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32)
                       if a.dtype == jnp.bfloat16 else a, prep.params)
    tokens = np.random.default_rng(0).integers(1, 256, (2, 16)).astype(
        np.int32)
    with use_dispatch(backend="jnp"), jax.default_matmul_precision("highest"):
        got = np.asarray(forward(p32, mc32, tokens=jnp.asarray(tokens)))
    rows = np.tile(np.arange(16, dtype=np.int32), (2, 1))
    want = ref.logits_at(11, cfg, sparsity, tokens, rows)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 1e-5, err


def test_program_tree_holds_reference_weights():
    cfg = smoke_config("compressed", (2, 4))
    params = adapter.program_params(ref, 5, cfg)
    from repro.core import nm

    w = ref.layer_weights(ref.seed_key(5), 1, cfg, (2, 4))
    leaf = params["stages"][0]["slot0"]["ffn"]["w_out"]
    dense = nm.decompress(leaf["values"][1, 0],
                          nm.unpack_meta(leaf["meta_packed"][1, 0]), 2, 4)
    np.testing.assert_array_equal(np.asarray(dense, np.float32),
                                  np.asarray(w["w_down"], np.float32))
    kept = np.asarray(w["w_down"] != 0).reshape(-1, 4, w["w_down"].shape[1])
    assert (kept.sum(axis=1) == 2).all()


def temp_bytes(jitted, key):
    return jitted.lower(key).compile().memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("layout,sparsity", [("dense", None),
                                             ("compressed", (2, 4))])
def test_build_holds_one_layers_dense_weights(layout, sparsity):
    """The compiled build at the MEDIUM size keeps at most one layer's
    dense weights alive beside the tree: six more layers add less than
    one layer's dense weights to its temporaries, and they are at most
    one layer's dense weights, the embedding and head, and the scratch
    that making the embedding and head, or one layer, takes alone (the
    CPU's threefry works in buffers several times its output; compiled
    for a v5e, the 2:4 build's temporaries are under the first two
    alone)."""
    cfg = dict(smoke_config(layout, sparsity), **MEDIUM)
    key = ref.seed_key(3)
    dense_layer = sum(2 * k * o for k, o in ref.linear_shapes(cfg).values())
    emb_head = 2 * 2 * cfg["vocab_size"] * cfg["hidden_size"]
    making = max(
        temp_bytes(jax.jit(lambda k: ref.embedding_weights(k, cfg)), key),
        temp_bytes(jax.jit(lambda k: ref.layer_weights(k, 0, cfg, sparsity)),
                   key))
    deep = temp_bytes(adapter.build(ref, cfg), key)
    shallow = temp_bytes(adapter.build(ref, dict(cfg, num_hidden_layers=2)),
                         key)
    assert cfg["num_hidden_layers"] == 8
    assert deep - shallow < dense_layer
    assert deep <= dense_layer + emb_head + making


def test_program_tree_matches_init_params_structure():
    from repro.models import init_params

    for layout, sparsity in (("dense", None), ("compressed", (2, 4))):
        cfg = smoke_config(layout, sparsity)
        spec = sut.serving_spec(cfg, smoke_mix(), backend="jnp")
        mc = spec.apply_to(adapter.model_config(cfg))
        want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mc))
        got = jax.eval_shape(lambda: adapter.program_params(ref, 0, cfg))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_seed_key_keeps_all_64_bits():
    a = ref.seed_key(2**33 + 1)
    b = ref.seed_key(1)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        ref.seed_key(-1)


def mix_file(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", ["chat_short"])
def test_traffic_draw_is_seeded_and_does_the_same_work(name):
    mix = mix_file(name)
    seed = 2**31 + 12345
    a = arrivals.segment(mix, seed, 3, 92544)
    b = arrivals.segment(mix, seed, 3, 92544)
    c = arrivals.segment(mix, seed + 1, 3, 92544)
    assert a == b
    assert len(a) == mix["segment_requests"]
    # another seed draws other tokens for the same sizes and arrivals
    assert [(len(r.prompt), r.max_new_tokens, r.arrival) for r in a] == \
        [(len(r.prompt), r.max_new_tokens, r.arrival) for r in c]
    assert all(r.prompt != s.prompt for r, s in zip(a, c))
    # every segment holds the same sizes, in an order of its own
    d = arrivals.segment(mix, seed, 4, 92544)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in d)
    assert sorted(r.max_new_tokens for r in a) == sorted(
        r.max_new_tokens for r in d)
    assert a[-1].arrival == pytest.approx(d[-1].arrival)
    assert [r.max_new_tokens for r in a] != [r.max_new_tokens for r in d]
    # the worst pairing of the longest prompt and answer fits a slot
    n = mix["segment_requests"]
    assert max(arrivals.prompt_lengths(mix, n)) + max(
        arrivals.output_lengths(mix, n)) <= mix["engine"]["max_len"]
    for r in a:
        assert all(0 < t < 92544 for t in r.prompt)


@pytest.mark.parametrize("name", ["chat_short"])
def test_traffic_file_rate_follows_its_rule(name):
    mix = mix_file(name)
    assert mix["rate_per_iteration"] == pytest.approx(
        arrivals.capacity_rate(mix), rel=1e-5)


def test_chat_short_keeps_the_published_means():
    """LMSYS-Chat-1M's means (69.5 prompt, 214.5 response tokens) come
    through, moved only by the cuts the mix file lists."""
    mix = mix_file("chat_short")
    n = 100_000
    raw = dict(mix, prompt_tokens={"mean": 69.5, "quantum": 1, "min": 0},
               output_tokens={"mean": 214.5, "min": 0})
    assert np.mean(arrivals.prompt_lengths(raw, n)) == pytest.approx(
        69.5, rel=2e-3)
    assert np.mean(arrivals.output_lengths(raw, n)) == pytest.approx(
        214.5, rel=2e-3)
    m = mix["segment_requests"]
    assert np.mean(arrivals.prompt_lengths(mix, m)) == pytest.approx(
        69.5, rel=0.03)
    outs = arrivals.output_lengths(mix, m)
    assert max(outs) == mix["output_tokens"]["max"]
    assert set(mix["reduced"]) == {"prompt_tokens.quantum",
                                   "output_tokens.max"}


@pytest.mark.parametrize("spec,n,want", [
    ({"values": [32, 64, 128], "p": [0.3, 0.4, 0.3]}, 10,
     [32, 32, 32, 64, 64, 64, 64, 128, 128, 128]),
    ({"mean": 10.0}, 4, [1, 5, 10, 21]),
    ({"mean": 100.0, "quantum": 16, "max": 64}, 4, [16, 48, 64, 64]),
    ({"median": 48, "sigma": 0.0, "min": 8, "max": 128}, 3, [48, 48, 48]),
    ({"median": 48, "sigma": 0.7, "min": 40, "max": 60}, 3, [40, 48, 60]),
])
def test_length_forms(spec, n, want):
    assert arrivals.lengths(spec, n) == want


def test_warmup_covers_every_prefill_chunk_length():
    chat = mix_file("chat_short")
    n = chat["segment_requests"]
    chunk = chat["engine"]["prefill_chunk"]
    want = set()
    for p in arrivals.prompt_lengths(chat, n):
        want |= {min(chunk, p - i) for i in range(0, p, chunk)}
    assert arrivals.chunk_lengths(chat) == sorted(want)
    assert [len(r.prompt) for r in arrivals.warmup_requests(chat)] == \
        sorted(want)

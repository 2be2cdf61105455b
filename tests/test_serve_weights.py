"""The served path casts a float32 master copy to the compute dtype once per
weight set (``lm.compute_params`` + ``serve.engine.WeightCache``).

Tiny f32-master configurations of both families: the SSM (mamba2) and
attention + MLP (yi).  The served logits must be bit-identical to the jitted
model programs run on the uncast tree; the cache must build each copy once,
for the very leaves it was built from, within its bound, under concurrent
first calls; a tree already in the compute dtype is served as it is."""

import gc
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import lm
from repro.serve import engine

ARCHS = ("mamba2-370m", "yi-9b")
# leaves the model reads at param_dtype, by family
KEPT = {"mamba2-370m": {"ln1", "final_norm", "A_log", "D", "dt_bias"},
        "yi-9b": {"ln1", "ln2", "final_norm"}}
BATCH, PROMPT, NEW = 2, 8, 5
MAX_LEN = PROMPT + NEW

_ref_prefill = jax.jit(lm.prefill, static_argnames=("cfg", "max_len"))
_ref_decode = jax.jit(lm.decode_step, static_argnames=("cfg",))


def _params(cfg, seed=0):
    return lm.init(jax.random.PRNGKey(seed), cfg)


def _prompt(cfg):
    return jax.random.randint(jax.random.PRNGKey(7), (BATCH, PROMPT), 0,
                              cfg.vocab)


def _counts():
    w = engine.weights
    return w.casts_built, w.cache_hits


def _leaf_names(tree):
    return {path[-1].key: x for path, x in
            jax.tree_util.tree_leaves_with_path(tree)}


def _serve(params, cfg):
    cache, logits = engine.prefill(params, cfg, _prompt(cfg), max_len=MAX_LEN)
    return cache, logits


@pytest.mark.parametrize("arch", ARCHS)
def test_served_logits_are_bit_identical_to_the_uncast_programs(arch):
    cfg = configs.get_smoke(arch)
    params = _params(cfg)
    prompt = _prompt(cfg)
    c_ref, l_ref = _ref_prefill(params, cfg, prompt, max_len=MAX_LEN)
    c_srv, l_srv = engine.prefill(params, cfg, prompt, max_len=MAX_LEN)
    np.testing.assert_array_equal(np.asarray(l_srv), np.asarray(l_ref))
    tok = jnp.argmax(l_ref[:, :cfg.vocab], axis=-1)[:, None]
    for _ in range(NEW):
        l_ref, c_ref = _ref_decode(params, cfg, tok, c_ref)
        l_srv, c_srv = engine.decode(params, cfg, tok, c_srv)
        np.testing.assert_array_equal(np.asarray(l_srv), np.asarray(l_ref))
        tok = jnp.argmax(l_ref[:, :cfg.vocab], axis=-1)[:, None]
    ids = engine.greedy_generate(params, cfg, prompt, NEW, max_len=MAX_LEN)
    assert ids.shape == (BATCH, NEW)


@pytest.mark.parametrize("arch", ARCHS)
def test_rule_casts_matrices_and_keeps_the_float32_reads(arch):
    cfg = configs.get_smoke(arch)
    params = _params(cfg)
    tree = lm.compute_params(params, cfg)
    src, out = _leaf_names(params), _leaf_names(tree)
    for name, x in out.items():
        if name in KEPT[arch]:
            assert x.dtype == cfg.pdtype, name
            assert x is src[name], name
        else:
            assert x.dtype == cfg.cdtype, name
    assert KEPT[arch] <= set(out)


@pytest.mark.parametrize("arch", ARCHS)
def test_a_second_call_builds_no_new_cast(arch):
    cfg = configs.get_smoke(arch)
    params = _params(cfg)
    built0, hits0 = _counts()
    cache, logits = _serve(params, cfg)
    assert _counts() == (built0 + 1, hits0)
    tok = jnp.argmax(logits, axis=-1)[:, None]
    engine.decode(params, cfg, tok, cache)
    _serve(params, cfg)
    assert _counts() == (built0 + 1, hits0 + 2)


@pytest.mark.parametrize("arch", ARCHS)
def test_new_weights_build_a_new_cast(arch):
    cfg = configs.get_smoke(arch)
    params = _params(cfg)
    _, before = _serve(params, cfg)
    built0, hits0 = _counts()

    _serve(_params(cfg), cfg)                          # a new tree
    assert _counts() == (built0 + 1, hits0)

    same_leaves = jax.tree.map(lambda x: x, params)    # new dicts, same leaves
    _serve(same_leaves, cfg)
    assert _counts() == (built0 + 1, hits0 + 1)

    block = params["blocks"]["s0"]["ssm" if cfg.ssm else "attn"]
    name = "wx" if cfg.ssm else "wq"
    block[name] = block[name] * 2                       # one leaf replaced
    _, after = _serve(params, cfg)
    assert _counts() == (built0 + 2, hits0 + 1)
    assert not np.array_equal(np.asarray(after), np.asarray(before))
    _, ref = _ref_prefill(params, cfg, _prompt(cfg), max_len=MAX_LEN)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(ref))


def test_cache_stays_within_its_bound_and_drops_what_callers_drop():
    cfg = configs.get_smoke("mamba2-370m")
    cap = engine.weights.capacity
    held = [_params(cfg, seed) for seed in range(cap + 2)]
    for p in held:
        _serve(p, cfg)
        assert len(engine.weights) <= cap
    cast = sum(x.nbytes for path, x in
               jax.tree_util.tree_leaves_with_path(held[-1])
               if path[-1].key not in KEPT["mamba2-370m"]) // 2
    assert engine.weights.bytes_held == cap * cast
    del held, p
    gc.collect()
    assert engine.weights.bytes_held == 0


def test_eight_concurrent_first_calls_build_one_cast():
    cfg = configs.get_smoke("mamba2-370m")
    params = _params(cfg, seed=11)
    prompt = _prompt(cfg)
    _ref_prefill(params, cfg, prompt, max_len=MAX_LEN)   # compile outside
    built0, hits0 = _counts()
    start = threading.Barrier(8)
    outs, errors = [None] * 8, []

    def call(i):
        try:
            start.wait(timeout=30)
            outs[i] = engine.prefill(params, cfg, prompt, max_len=MAX_LEN)[1]
        except Exception as e:                            # reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=call, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert _counts() == (built0 + 1, hits0 + 7)
    for out in outs[1:]:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(outs[0]))


@pytest.mark.parametrize("arch", ARCHS)
def test_weights_in_the_compute_dtype_are_served_as_they_are(arch):
    cfg = configs.get_smoke(arch).replace(param_dtype="bfloat16")
    params = _params(cfg)
    assert lm.compute_params(params, cfg) is params
    assert engine.weights.get(params, cfg) is params
    bytes0 = engine.weights.bytes_held
    built0, hits0 = _counts()
    _serve(params, cfg)
    assert _counts() == (built0, hits0 + 1)
    assert engine.weights.bytes_held == bytes0


@pytest.mark.parametrize("arch", ARCHS)
def test_lowered_programs_keep_their_names_and_take_compute_dtype_matrices(
        arch):
    cfg = configs.get_smoke(arch)
    params = lm.init_shapes(cfg)
    prompt = jax.ShapeDtypeStruct((BATCH, PROMPT), jnp.int32)
    lowered = engine.prefill.lower(params, cfg, prompt, max_len=MAX_LEN)
    cache, _ = jax.eval_shape(
        lambda p, t: lm.prefill(p, cfg, t, max_len=MAX_LEN), params, prompt)
    token = jax.ShapeDtypeStruct((BATCH, 1), jnp.int32)
    lowered_dec = engine.decode.lower(params, cfg, token, cache)
    for low, name in ((lowered, "jit_prefill"), (lowered_dec, "jit_decode_step")):
        assert low.as_text().startswith(f"module @{name} ")
        args = _leaf_names(low.args_info[0][0])
        for leaf, info in args.items():
            want = cfg.pdtype if leaf in KEPT[arch] else cfg.cdtype
            assert info.dtype == want, (name, leaf)
        low.compile()

"""bench/flops.py against hand counts at a smoke size and at the cells'
sizes, and its parameter count, matrices and vectors apart, against the
program's own parameter tree."""

import json
import math
import os
import sys

import jax
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
from harness.stage import model_config  # noqa: E402
from repro.models import lm  # noqa: E402

MAMBA = {"name": "m", "family": "ssm", "n_layers": 2, "d_model": 64,
         "n_heads": 16, "n_kv_heads": 16, "d_ff": 0, "vocab": 512,
         "layer_pattern": ["ssm"], "tie_embeddings": True,
         "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
                 "chunk": 16},
         "rms_eps": 1e-5, "rope_theta": 10000.0,
         "param_dtype": "float32", "compute_dtype": "bfloat16"}
LLAMA = {"name": "y", "family": "dense", "n_layers": 2, "d_model": 64,
         "n_heads": 8, "n_kv_heads": 2, "d_ff": 128, "vocab": 512,
         "layer_pattern": ["attn"], "tie_embeddings": False,
         "rms_eps": 1e-6, "rope_theta": 10000.0,
         "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}


def test_mamba_prefill_by_hand():
    # one layer, L=32: d=64, d_inner=128, 8 heads of 16, N=16, conv 4, chunk 16
    proj = 2 * 32 * 64 * (2 * 128 + 2 * 16 + 8) + 2 * 32 * 128 * 64
    conv = 2 * 32 * 4 * (128 + 32)
    pairs = 2 * (16 * 17 // 2)                 # two chunks, causal pairs
    ssd = 2 * pairs * 16 + 2 * pairs * 8 * 16 + 2 * 2 * 32 * 8 * 16 * 16
    head = 2 * 64 * 512
    assert (proj, conv, ssd) == (1736704, 40960, 340480)
    assert flops.prefill_flops(MAMBA, 1, 32) == 2 * (proj + conv + ssd) + head
    assert flops.prefill_flops(MAMBA, 3, 32) == 3 * 4301824


def test_llama_prefill_and_decode_by_hand():
    # one layer, L=32: 8 heads of 8, 2 kv heads, d_ff 128
    proj = 2 * 32 * 64 * (64 + 2 * 16) + 2 * 32 * 64 * 64
    attn = 2 * 2 * 8 * 8 * (32 * 33 // 2)
    mlp = 2 * 32 * 3 * 64 * 128
    assert proj + attn + mlp == 2363392
    assert flops.prefill_flops(LLAMA, 1, 32) == 2 * 2363392 + 2 * 64 * 512
    # a decode step at position 32 attends 33 keys
    step = 2 * 64 * 96 + 2 * 64 * 64 + 2 * 2 * 64 * 33 + 2 * 3 * 64 * 128
    assert flops.decode_flops(LLAMA, 2, 32) == 2 * (2 * step + 2 * 64 * 512)


def test_request_is_prefill_then_new_tokens_minus_one_steps():
    req = {"prompts": 4, "prompt_len": 32, "new_tokens": 5}
    want = flops.prefill_flops(LLAMA, 4, 32) + sum(
        flops.decode_flops(LLAMA, 4, 32 + i) for i in range(4))
    assert flops.request_flops(LLAMA, req) == want


# leaves the math reads in float32 whatever the master dtype
_VECTORS = {"ln1", "ln2", "A_log", "D", "dt_bias"}


def _program_block_leaves(model):
    cfg = model_config(model)
    shapes = jax.eval_shape(lambda k: lm.init(k, cfg), jax.random.PRNGKey(0))
    return jax.tree_util.tree_flatten_with_path(shapes["blocks"])[0]


def _program_layer_params(model):
    blocks = _program_block_leaves(model)
    return sum(math.prod(s.shape) for _, s in blocks) // model["n_layers"]


def test_layer_params_match_the_program_tree():
    assert flops.layer_params(MAMBA, "ssm") == 28024 == _program_layer_params(MAMBA)
    assert flops.layer_params(LLAMA, "attn") == 34944 == _program_layer_params(LLAMA)


@pytest.mark.parametrize("model,kind", [(MAMBA, "ssm"), (LLAMA, "attn")])
def test_layer_split_matches_the_program_tree(model, kind):
    split = {True: 0, False: 0}
    for path, s in _program_block_leaves(model):
        split[path[-1].key in _VECTORS] += math.prod(s.shape) // model["n_layers"]
    mats, vecs = flops.layer_split(model, kind)
    assert (mats, vecs) == (split[False], split[True])
    assert mats + vecs == flops.layer_params(model, kind)


def test_decode_bytes_by_hand():
    # tied head: the whole table is read for the logits; f32 master weights
    # under bf16 compute: matrices at 2 bytes, vectors (A_log D dt_bias ln1,
    # final norm) at 4
    assert flops.layer_split(MAMBA, "ssm") == (27936, 88)
    assert flops.decode_weight_bytes(MAMBA, 4) == \
        (2 * 27936 + 512 * 64) * 2 + (2 * 88 + 64) * 4
    # untied: the head plus the batch's embedding rows, bf16
    assert flops.decode_weight_bytes(LLAMA, 4) == \
        (2 * 34944 + 64 + 512 * 64 + 4 * 64) * 2
    # a KV step at position 32 reads 33 rows and writes one, k and v, bf16
    assert flops.decode_state_bytes(LLAMA, 1, 32) == 2 * (2 * 34 * 2 * 8 * 2)
    # the SSM state [H, P, N] f32 and the conv tails, read and written
    assert flops.decode_state_bytes(MAMBA, 1, 32) == \
        2 * 2 * (8 * 16 * 16 * 4 + 3 * (128 + 32) * 2)


def test_master_dtype_moves_only_the_vectors():
    # at bf16 compute, a f32 master copy adds only the vectors' extra bytes
    bf16 = dict(MAMBA, param_dtype="bfloat16")
    vecs = 2 * flops.layer_split(MAMBA, "ssm")[1] + MAMBA["d_model"]
    assert flops.decode_weight_bytes(MAMBA, 4) \
        - flops.decode_weight_bytes(bf16, 4) == vecs * (4 - 2)
    assert flops.decode_state_bytes(MAMBA, 4, 32) == \
        flops.decode_state_bytes(bf16, 4, 32)


@pytest.mark.parametrize("config,traffic,weights,mean", [
    ("qa-mamba2-370m", "short-burst", 736589824, 1144551424),
    ("qa-yi-9b", "longdoc-closed8", 6061072384, 6197518336),
])
def test_cells_decode_bytes(config, traffic, weights, mean):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        model = json.load(f)["model"]
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        request = json.load(f)["request"]
    assert flops.decode_weight_bytes(model, request["prompts"]) == weights
    assert flops.mean_decode_bytes(model, request) == mean

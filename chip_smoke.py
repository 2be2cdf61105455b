"""Bring-up check on one TPU chip: the paper's QA-inference workflow with a
full-width mamba2-370m stage.

    python chip_smoke.py [--seed N]        # from the repository root

The workflow is ``benchmarks/common.qa_spec("joint")``: ``sort`` on
``aws/lambda`` then ``qa`` on ``aliyun/fc_gpu``, deployed with
``repro.core.workflow.deploy`` onto the in-process ``LocalRunner``.  ``sort``
builds one request from the seed (4 prompts of 1024 tokens, the paper's four
questions over one document); ``qa`` serves mamba2-370m at its published
widths (random weights from ``--seed``) through ``serve.engine``: prefill
plus 32 greedy decode steps.  Four instances are submitted at once.

Checks: every instance ends with exactly one committed ``qa`` output, the
runner drops nothing, token ids are in the vocabulary, and the served bf16
last-token logits of a 128-token prompt agree with a float32 reference run
on the host CPU within ``REF_ERR_BOUND``.

Everything runs in this one process — the chip belongs to one process.  The
last line of standard output is ``{"ok": true, "device": {...}}``, printed
only when every check passed; without a TPU the script exits non-zero
before any work.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.join(_ROOT, "benchmarks")]

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax import monitoring                                    # noqa: E402

import common                                                 # noqa: E402
from repro import configs                                     # noqa: E402
from repro.backends.localjax import LocalRunner               # noqa: E402
from repro.compile_cache import use_compile_cache             # noqa: E402
from repro.core import workflow as wf                         # noqa: E402
from repro.core.jlobject import fits_quota                    # noqa: E402
from repro.models import lm                                   # noqa: E402
from repro.serve import engine                                # noqa: E402

# max |served bf16 − float32 reference| over the std of the reference's
# last-token logits.  The full-width rehearsal with both paths on the host
# CPU measured 0.264, 0.331 and 0.293 for seeds 0, 1 and 2; logits that
# shared nothing with the reference would be off by several std.
REF_ERR_BOUND = 0.5

ARCH = "mamba2-370m"
REQUESTS = 4                     # workflow instances, all submitted at once
PROMPTS = 4                      # questions per request

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


@dataclass(frozen=True)
class Sizes:
    """What one run serves.  ``FULL`` is the bring-up size; tests pass a
    reduced one (``smoke`` selects the same-family reduced config)."""

    smoke: bool = False
    prompt_len: int = 1024
    new_tokens: int = 32
    ref_len: int = 128           # prompt of the float32 reference check


FULL = Sizes()


def _say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def _max_in_flight(spans) -> int:
    """Largest number of [start, end] intervals that overlap at once."""
    edges = sorted([(s, 1) for s, _ in spans] + [(e, -1) for _, e in spans],
                   key=lambda t: (t[0], t[1]))
    live = peak = 0
    for _, d in edges:
        live += d
        peak = max(peak, live)
    return peak


def main(argv=None, *, platform: str = "tpu", sizes: Sizes = FULL) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != platform:
        print(f"chip_smoke: the default JAX device is {dev.platform} "
              f"({dev.device_kind}), not {platform}", file=sys.stderr)
        return 1
    _say("device_kind", dev.device_kind)
    _say("compile_cache_dir", use_compile_cache())

    compiles: list = []
    cache_events = {_CACHE_HIT: 0, _CACHE_MISS: 0}

    def on_duration(event: str, secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            compiles.append(secs)

    def on_event(event: str, **_) -> None:
        if event in cache_events:
            cache_events[event] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        return _run(args.seed, sizes, dev, compiles, cache_events)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)


def _run(seed: int, sizes: Sizes, dev, compiles: list,
         cache_events: dict) -> int:
    cfg = (configs.get_smoke if sizes.smoke else configs.get)(ARCH)
    vocab = cfg.vocab
    max_len = sizes.prompt_len + sizes.new_tokens
    failures = []

    # ---- model: published widths, random weights from the seed -----------
    t0 = time.perf_counter()
    params = jax.block_until_ready(lm.init(jax.random.PRNGKey(seed), cfg))
    _say("model", f"{cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
                  f"vocab={vocab} params={cfg.param_count() / 1e6:.1f}M")
    _say("init_s", time.perf_counter() - t0)

    # ---- compile the served programs (set-up, outside the window) ---------
    prompt0 = jnp.zeros((PROMPTS, sizes.prompt_len), jnp.int32)
    t0 = time.perf_counter()
    engine.prefill.lower(params, cfg, prompt0, max_len=max_len).compile()
    _say("compile_prefill_s", time.perf_counter() - t0)
    cache, logits = engine.prefill(params, cfg, prompt0, max_len=max_len)
    tok0 = jnp.argmax(logits[:, :vocab], axis=-1)[:, None]
    t0 = time.perf_counter()
    engine.decode.lower(params, cfg, tok0, cache).compile()
    _say("compile_decode_s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    np.asarray(engine.greedy_generate(params, cfg, prompt0, sizes.new_tokens))
    _say("warmup_request_s", time.perf_counter() - t0)
    del cache, logits

    # ---- the workflow: sort (aws/lambda) → qa (aliyun/fc_gpu) --------------
    def sort(req):
        rng = np.random.default_rng((req["seed"], req["request"]))
        return rng.integers(0, vocab, (PROMPTS, sizes.prompt_len)).tolist()

    def qa(prompts):
        prompt = jnp.asarray(np.asarray(prompts, np.int32))
        out = engine.greedy_generate(params, cfg, prompt, sizes.new_tokens)
        return np.asarray(out).tolist()

    spec = common.qa_spec("joint")
    spec.functions["sort"].workload.fn = sort
    spec.functions["qa"].workload.fn = qa
    qa_faas = spec.functions["qa"].faas

    runner = LocalRunner()
    dep = wf.deploy(runner, spec)
    quota = runner.faas[qa_faas].payload_quota
    by_ds = not fits_quota(sort({"seed": seed, "request": 0}), quota)
    _say("sort_to_qa_transfer", f"{'datastore' if by_ds else 'direct'} "
                                f"(quota {quota} B on {qa_faas})")

    n_compiles = len(compiles)
    wids = [dep.start({"seed": seed, "request": i})
            for i in range(REQUESTS)]
    wall_ms = runner.run(timeout_s=900.0)
    _say("workflow_wall_ms", wall_ms)
    _say("compiles_in_window", len(compiles) - n_compiles)

    spans, tokens = [], 0
    for w in wids:
        recs = dep.executions(w)
        done = [r for r in recs if r.function == "qa" and r.status == "done"]
        if len(done) != 1:
            failures.append(f"{w}: {len(done)} committed qa outputs")
            continue
        out = np.asarray(done[0].result)
        if out.shape != (PROMPTS, sizes.new_tokens):
            failures.append(f"{w}: output shape {out.shape}")
        if out.min() < 0 or out.max() >= vocab:
            failures.append(f"{w}: token ids outside [0, {vocab})")
        tokens += out.size
        spans.append((min(r.t_queued for r in recs),
                      max(r.t_end for r in recs if r.t_end is not None)))
    ms = [dep.makespan_ms(w) for w in wids]
    _say("request_ms", [round(m, 1) for m in ms])
    _say("request_ms_mean", float(np.mean(ms)))
    _say("tokens_generated", tokens)
    in_flight = _max_in_flight(spans)
    _say("max_instances_in_flight", in_flight)
    _say("drop_count", runner.drop_count)
    if runner.drop_count:
        failures.append(f"{runner.drop_count} dropped invocations")
    if in_flight < 2:
        failures.append(f"only {in_flight} instance(s) in flight at once")

    # ---- float32 reference on the host CPU --------------------------------
    ref_prompt = np.random.default_rng(seed).integers(
        0, vocab, (1, sizes.ref_len), dtype=np.int32)
    _, served = engine.prefill(params, cfg, jnp.asarray(ref_prompt),
                               max_len=sizes.ref_len)
    served = np.asarray(served[:, :vocab], np.float32)
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        _, ref = engine.prefill(jax.device_put(params, cpu),
                                cfg.replace(compute_dtype="float32"),
                                jax.device_put(ref_prompt, cpu),
                                max_len=sizes.ref_len)
    ref = np.asarray(ref[:, :vocab], np.float32)
    err = float(np.max(np.abs(served - ref)) / np.std(ref))
    _say("ref_err_max_over_std", err)
    _say("ref_err_bound", REF_ERR_BOUND)
    if not np.isfinite(err) or err > REF_ERR_BOUND:
        failures.append(f"reference error {err} above {REF_ERR_BOUND}")

    stats = dev.memory_stats() or {}
    _say("peak_bytes_in_use", stats.get("peak_bytes_in_use", "not reported"))
    _say("compile_cache", f"hits={cache_events[_CACHE_HIT]} "
                          f"misses={cache_events[_CACHE_MISS]}")

    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

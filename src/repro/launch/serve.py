"""Serving launcher: prefill + greedy decode with batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \\
        --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.compile_cache import use_compile_cache
from repro.models import lm
from repro.serve.engine import greedy_generate


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(configs.ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    if cfg.enc_dec or cfg.n_patches:
        print(f"[serve] note: {cfg.name} needs modality inputs; serving the "
              f"text decoder against stub frontends")
    key = jax.random.PRNGKey(args.seed)
    params = lm.init(key, cfg)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab)

    t0 = time.time()
    if cfg.enc_dec:
        frames = jax.random.normal(key, (args.batch, args.prompt_len // 8, 1024))
        cache, logits = lm.prefill(params, cfg, prompt,
                                   max_len=args.prompt_len + args.gen,
                                   frames=frames)
        toks = [np.argmax(np.asarray(logits), -1)[:, None]]
        decode = jax.jit(lambda p, t, c: lm.decode_step(p, cfg, t, c))
        for _ in range(args.gen - 1):
            logits, cache = decode(params, jax.numpy.asarray(toks[-1]), cache)
            toks.append(np.argmax(np.asarray(logits), -1)[:, None])
        out = np.concatenate(toks, axis=1)
    else:
        out = np.asarray(greedy_generate(params, cfg, prompt, args.gen))
    dt = time.time() - t0                  # np.asarray waited for the device
    tps = args.batch * args.gen / dt
    dev = jax.devices()[0]
    print(f"[serve] {cfg.name}: batch {args.batch} × prompt {args.prompt_len} "
          f"→ {args.gen} tokens in {dt:.2f}s, compilation included "
          f"({tps:.1f} tok/s on {dev.platform} {dev.device_kind})")
    print(f"[serve] sample continuation ids: {out[0][:16].tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

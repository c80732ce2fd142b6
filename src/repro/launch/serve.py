"""Serving driver: batched greedy generation with a migratable session.

Demonstrates the paper's workflow on the serving side: generate k tokens,
dump the session (KV caches + output cursor), kill the process, restore on
"another machine" (fresh process / different mesh), continue — outputs are
bitwise identical to an uninterrupted run (tests/test_serving.py).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --tiny \
      --prompt-len 16 --gen 32 --batch 4 --ckpt-dir /tmp/serve_ck \
      --ckpt-at 10 [--resume]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import Checkpointer, serve_meta
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import LM
from repro.serving import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=configs.ARCH_NAMES)
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-at", type=int, default=0,
                    help="dump session after this many generated tokens")
    ap.add_argument("--stop-after-ckpt", action="store_true")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = configs.get_tiny(args.arch) if args.tiny \
        else configs.get_config(args.arch)
    lm = LM(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = lm.init(key)
    max_len = args.prompt_len + args.gen + 1
    eng = ServeEngine(lm, params, max_len=max_len, donate_cache=False)
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None

    if args.resume:
        assert ckpt and ckpt.registry.latest(), "nothing to resume"
        state, man = ckpt.load_latest()
        state = jax.tree.map(jnp.asarray, state)
        eng.restore_session(state)
        print(f"[serve] resumed session at token "
              f"{len(eng.out_tokens)} from {man['image_id']}")
    else:
        prompts = np.asarray(jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab_size))
        eng.submit(prompts)

    def maybe_ckpt(e):
        n = len(e.out_tokens)
        if ckpt and args.ckpt_at and n == args.ckpt_at:
            ckpt.save(e.session_state(), step=n,
                      meta=serve_meta(arch=cfg.name, tokens_done=n))
            print(f"[serve] session dumped at token {n}")
            if args.stop_after_ckpt:
                raise SystemExit(0)

    out = eng.generate(args.gen, on_token=maybe_ckpt)
    print("[serve] generated tokens:")
    for b in range(out.shape[0]):
        print(" ", out[b].tolist())


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Which programs a change touched, without the chip: the sha-256 of the
lowered text of every program `Engine.warmup()` compiles, by `_jit_handles`
name and argument signature, for one tiny configuration under the chip
cells' flags (w8a8, 16-step windows, mixed steps).

    python scripts/lowered_text_check.py --model tiny-kimi-ep4-debug --out a.json
    (cd <a checkout of the parent> && python <this file> --model ... --out b.json)
    python scripts/lowered_text_check.py --compare a.json b.json

The check PR 29 and PR 31 made by hand (.claude/skills/verify/SKILL.md)."""
import argparse
import hashlib
import json
import os
import sys


def record(model: str) -> dict:
    sys.path.insert(0, os.getcwd())
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from dynamo_tpu.engine import engine as eng_mod
    from dynamo_tpu.engine.config import EngineConfig

    seen: dict = {}

    class Proxy:
        def __getattr__(self, name):
            return getattr(jax, name)

        def jit(self, fn, **kw):
            real = jax.jit(fn, **kw)
            label = getattr(fn, "__name__", repr(fn))

            def call(*args, **kwargs):
                sig = str(jax.tree.map(
                    lambda a: (getattr(a, "shape", None),
                               str(getattr(a, "dtype", type(a)))),
                    (args, kwargs)))
                key = label + "|" + hashlib.sha256(sig.encode()).hexdigest()[:12]
                if key not in seen:
                    text = real.lower(*args, **kwargs).as_text()
                    seen[key] = hashlib.sha256(text.encode()).hexdigest()
                return real(*args, **kwargs)

            call._cache_size = real._cache_size
            return call

    eng_mod.jax = Proxy()
    eng = eng_mod.Engine(EngineConfig(
        model=model, page_size=4, num_pages=256, max_num_seqs=4,
        max_seq_len=128, mixed_batch_tokens=16, num_scheduler_steps=16,
        quantization="w8a8"))
    eng.warmup()
    return dict(sorted(seen.items()))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model")
    p.add_argument("--out")
    p.add_argument("--compare", nargs=2)
    a = p.parse_args()
    if a.compare:
        x, y = (json.load(open(f)) for f in a.compare)
        differ = sorted(k for k in set(x) | set(y) if x.get(k) != y.get(k))
        print(json.dumps({"programs": [len(x), len(y)], "differ": differ}))
        return 1 if differ else 0
    out = record(a.model)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"{a.model}: {len(out)} programs")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Serving example: batched greedy decode with a KV cache.

Covers three cache disciplines in one run: full KV (granite), sliding-
window ring buffer (h2o-danube), and O(1) recurrent state (mamba2).

    PYTHONPATH=src python examples/serve_decode.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.config import get_arch, reduced_config
from repro.models.lm import build_model
from repro.serve.engine import Request, ServeEngine


def main():
    for arch in ("granite-3-2b", "h2o-danube-1.8b", "mamba2-370m"):
        cfg = reduced_config(get_arch(arch))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        engine = ServeEngine(model, params, cache_len=96)
        reqs = [Request(prompt=[1, 2, 3, 4], max_new_tokens=12),
                Request(prompt=[9, 8, 7], max_new_tokens=12),
                Request(prompt=[5], max_new_tokens=12)]
        t0 = time.time()
        out = engine.generate(reqs)
        dt = time.time() - t0
        total = sum(len(r.out_tokens) for r in out)
        print(f"{arch:18s} generated {total} tokens in {dt:.2f}s "
              f"({total/dt:.1f} tok/s, batch={len(reqs)})")
        print(f"  sample: {out[0].out_tokens}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

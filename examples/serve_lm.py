"""Serve a small LM with continuously-batched requests (the serving path of
the assigned architectures; the production-mesh variant is exercised by the
decode/prefill dry-run cells).

    PYTHONPATH=src python examples/serve_lm.py [--arch llama3-8b]
"""
import argparse
import time

import jax
import numpy as np

from repro.arch import model as M
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.serve import Request, ServeEngine


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()

    cfg = get_config(args.arch + "-smoke")      # CPU-scale same-family config
    print(f"[serve] {cfg.name}: {M.param_count(cfg)/1e6:.2f}M params, "
          f"{args.slots} cache slots")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_slots=args.slots, max_seq=128)

    rng = np.random.default_rng(1)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(4, 16))
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=int(rng.integers(8, 24))))
        engine.submit(reqs[-1])

    t0 = time.perf_counter()
    total = engine.run_until_idle()
    dt = time.perf_counter() - t0
    done = sum(r.done for r in reqs)
    print(f"[serve] {done}/{len(reqs)} requests finished, {total} tokens "
          f"in {dt:.1f}s  ({total/dt:.1f} tok/s, {engine.steps} engine steps, "
          f"mean batch occupancy "
          f"{total/max(engine.steps,1):.2f}/{args.slots})")
    for r in reqs[:3]:
        print(f"  req{r.rid}: prompt[{len(r.prompt)}] -> {r.tokens[:8]}...")


if __name__ == "__main__":
    main()

"""Serving example: continuous batching over a small LM.

Boots the qwen3-family smoke model, submits a mixed-length request
stream through the v2 ``Engine``, and serves it with the slot-based
scheduler — the same prefill / decode steps the dry-run's serve cells
lower at 256/512-chip scale.  (See ``serve_stream.py`` for the
streaming / mid-run-admission / cancel surface.)

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import time

import jax
import numpy as np

from repro import configs as C
from repro import models as MZ
from repro.serving import Engine, ServeConfig
from repro.launch.mesh import make_mesh


def main():
    cfg = C.get_reduced("qwen3-0.6b")
    mesh = make_mesh()
    with jax.set_mesh(mesh):
        params = MZ.init_model(jax.random.key(0), cfg)

    scfg = ServeConfig(slots=4, max_len=256, prompt_pad=32,
                       max_new_tokens=24, temperature=0.0, eos_token=-1)
    engine = Engine(cfg, mesh, scfg, params)

    rng = np.random.default_rng(0)
    n_requests = 10
    handles = []
    for i in range(n_requests):
        L = int(rng.integers(4, 32))
        handles.append(engine.submit(
            rng.integers(0, 1000, size=L).astype(np.int32)))
    print(f"submitted {n_requests} requests (len 4..31) into "
          f"{scfg.slots} slots")

    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in done)
    ttfts = engine.ttfts_s()
    print(f"served {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s on 1 CPU core, median TTFT "
          f"{1e3 * sorted(ttfts)[len(ttfts) // 2]:.0f} ms)")
    for h in handles[:3]:
        print(f"  req {h.uid}: → {h.tokens[:8]}...")
    assert len(done) == n_requests
    print("ok")


if __name__ == "__main__":
    main()

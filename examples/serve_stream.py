"""Streaming-serving example: the v2 ``Engine`` API.

Demonstrates the request-level serving surface on a tiny LM:

  * ``submit()`` returns a handle immediately — no drain-the-queue call;
  * ``step()`` is one scheduler tick (admit + prefill + one decode
    chunk, a single device→host sync) returning ``TokenEvent``s;
  * requests submitted *mid-run* are admitted into slots freed by
    earlier requests, without stalling the live ones;
  * iterating a handle streams its tokens in order;
  * ``cancel()`` retires a request at the next chunk boundary.

Run:  PYTHONPATH=src python examples/serve_stream.py
(CI runs it as a non-blocking smoke step in the bench-smoke job.)
"""

import time

import jax
import numpy as np

from repro import models as MZ
from repro.models.config import ModelConfig
from repro.serving import Engine, RequestStatus, ServeConfig
from repro.launch.mesh import make_mesh

CFG = ModelConfig(name="stream-demo", n_layers=2, d_model=64,
                  vocab_size=512, n_heads=4, n_kv_heads=2, d_ff=128,
                  remat=False)


def main():
    mesh = make_mesh()
    with jax.set_mesh(mesh):
        params = MZ.init_model(jax.random.key(0), CFG)

    scfg = ServeConfig(slots=2, max_len=128, prompt_pad=16,
                       max_new_tokens=12, decode_chunk=4,
                       eos_token=-1, page_size=16)
    engine = Engine(CFG, mesh, scfg, params)
    rng = np.random.default_rng(0)

    def prompt(n):
        return rng.integers(1, CFG.vocab_size, size=n).astype(np.int32)

    t0 = time.time()
    # two requests up front, driven tick by tick
    a = engine.submit(prompt(9), max_new=8, stream=True)
    b = engine.submit(prompt(14), max_new=12, stream=True)
    events = engine.step()
    print(f"tick 1: {len(events)} tokens from "
          f"{sorted({e.uid for e in events})} (one host sync)")

    # mid-run submission: c queues now, lands in the slot a frees
    c = engine.submit(prompt(5), max_new=6, stream=True)
    while not a.done:
        engine.step()
    print(f"req {a.uid} done after {len(a.tokens)} tokens "
          f"(TTFT {1e3 * a.ttft_s:.1f} ms)")

    # cancel b: the next chunk boundary retires its slot + pages
    b.cancel()
    n_b = len(b.tokens)

    # stream the rest of c — iterating the handle drives step(); its
    # admission happened mid-run, into a slot a or b freed
    streamed = list(c)
    dt = time.time() - t0
    print(f"req {c.uid} admitted mid-run → slot {c.slot}, "
          f"streamed {streamed}")
    assert streamed == c.tokens and len(streamed) == 6
    assert b.status is RequestStatus.CANCELLED
    assert len(b.tokens) == n_b, "cancelled request emitted after cancel"
    assert engine._backend.free_pages and a.status is RequestStatus.DONE
    print(f"served {len(engine.finished)} requests "
          f"({engine.sync_count} host syncs) in {dt:.1f}s")
    print("ok")


if __name__ == "__main__":
    main()

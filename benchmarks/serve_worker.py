"""Subprocess worker for the ``serve`` benchmark table (DESIGN.md §13).

Receives a JSON spec on argv[1]:

    {"arch": "tinyllama-1.1b", "requests": 30, "max_new": 16,
     "n_slots": 8, "page_size": 16, "prefill_chunk": 16, "max_len": 64}

and prints one ``SERVE_ROWS <json list>`` line with two timed rows over the
SAME seeded mixed-length request set:

  * ``engine``     — the continuous-batching ``ServeEngine`` (paged KV
                     cache, ``n_slots`` in-flight sequences); per-token
                     latency percentiles come from the telemetry
                     ``StepTimer`` on the decode phase (every batched
                     decode step emits one token per in-flight sequence);
  * ``sequential`` — the pre-engine baseline: one dense-cache
                     ``sequential_generate`` call per request, in order.

Both rows are compile-warmed first (a throwaway pass over one request of
each prompt length; the module-level jitted step makes the timed pass reuse
the cache), and the engine's greedy tokens are checked bit-identical to the
sequential baseline before any timing is reported — the throughput gate
(``engine tokens/s >= 1.5x sequential`` at ``n_slots=8``) only counts if
the outputs match.
"""
import json
import os
import sys
import time

# a CPU study by design: pin the platform so it never takes the chip
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.serve import ServeEngine, sequential_generate  # noqa: E402
from repro.serve.__main__ import make_requests  # noqa: E402

SPEC = json.loads(sys.argv[1])


def run_sequential(params, cfg, reqs):
    outs = []
    for r in reqs:
        toks = sequential_generate(
            params, cfg, jnp.asarray([r.prompt], jnp.int32),
            gen_len=r.max_new, cache_len=len(r.prompt) + r.max_new)
        outs.append(tuple(int(t) for t in np.asarray(toks[0, len(r.prompt):])))
    return outs


def main():
    arch = SPEC.get("arch", "tinyllama-1.1b")
    cfg = get_config(arch, reduced=True)
    params = tf.init_lm(jax.random.PRNGKey(0), cfg)
    reqs = make_requests(SPEC.get("requests", 30), cfg.vocab_size, seed=0,
                         max_new=SPEC.get("max_new", 16))
    n_tok = sum(r.max_new for r in reqs)
    eng_kw = dict(n_slots=SPEC.get("n_slots", 8),
                  page_size=SPEC.get("page_size", 16),
                  max_len=SPEC.get("max_len", 64),
                  prefill_chunk=SPEC.get("prefill_chunk", 16))

    # warm both paths: one request per distinct prompt length
    by_len = {len(r.prompt): r for r in reqs}
    warm = list(by_len.values())
    ServeEngine(params, cfg, **eng_kw).run(warm)
    run_sequential(params, cfg, warm)

    # timed engine pass on a FRESH engine (timers then hold only this pass;
    # the module-level jitted step reuses the warm compile cache)
    eng = ServeEngine(params, cfg, **eng_kw)
    t0 = time.time()
    outs = eng.run(reqs)
    wall_eng = time.time() - t0

    t0 = time.time()
    base = run_sequential(params, cfg, reqs)
    wall_seq = time.time() - t0

    mismatches = sum(o.tokens != b for o, b in zip(outs, base))
    st = eng.stats()
    dec = st["phases"]["decode"]
    rows = [
        {"mode": "engine", "arch": cfg.name, "requests": len(reqs),
         "max_new": reqs[0].max_new, "n_slots": eng_kw["n_slots"],
         "page_size": eng_kw["page_size"], "tokens": n_tok,
         "wall_s": wall_eng, "tokens_per_s": n_tok / wall_eng,
         "p50_token_latency_s": dec.get("p50_s", 0.0),
         "p95_token_latency_s": dec.get("p95_s", 0.0),
         "peak_cache_bytes": st["peak_cache_bytes"],
         "pool_bytes": st["pool_bytes"],
         "prefill_mean_s": st["phases"]["prefill"].get("mean_s", 0.0),
         "schedule_mean_s": st["phases"]["schedule"].get("mean_s", 0.0),
         "mismatches": mismatches},
        {"mode": "sequential", "arch": cfg.name, "requests": len(reqs),
         "max_new": reqs[0].max_new, "tokens": n_tok, "wall_s": wall_seq,
         "tokens_per_s": n_tok / wall_seq,
         "p50_token_latency_s": wall_seq / n_tok,
         "p95_token_latency_s": wall_seq / n_tok,
         "mismatches": mismatches},
    ]
    platform = jax.devices()[0].platform
    print("SERVE_ROWS " + json.dumps(
        [dict(r, platform=platform) for r in rows]))


if __name__ == "__main__":
    main()

"""Render the §Kernels / §Serve markdown tables from the CPU benchmark
JSON that ``benchmarks.run --json`` writes.

    PYTHONPATH=src python -m repro.launch.report --what kernels \
        --bench-kernels BENCH_kernels.json [--out report.md]

Table/formatting helpers live in ``repro.telemetry.report`` (the shared
markdown machinery — DESIGN.md §10).  An absent or unreadable file renders
a placeholder line instead of raising.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.telemetry.report import markdown_table


def serve_table(path: str) -> str:
    """§Serve table from a ``BENCH_serve.json`` (benchmarks.run --only
    serve): tokens/s + per-token latency percentiles for the continuous-
    batching engine vs the sequential dense-cache baseline.  Tolerates an
    absent/empty file (serving benches are optional artifacts)."""
    if not os.path.exists(path):
        return f"*no serve bench found at {path}*"
    try:
        rows = json.load(open(path))
    except (OSError, json.JSONDecodeError):
        return f"*unreadable serve bench at {path}*"
    by_mode = {}
    out = []
    for r in rows:
        mode = r.get("name", "").rsplit("/", 1)[-1] or r.get("mode", "?")
        by_mode[mode] = r
        out.append([
            r.get("name", mode),
            f"{r.get('tokens_per_s', 0.0):.1f}",
            f"{r.get('p50_token_ms', 0.0):.3f}",
            f"{r.get('p95_token_ms', 0.0):.3f}",
            str(int(r["peak_cache_bytes"]))
            if "peak_cache_bytes" in r else "-",
            str(int(r.get("mismatches", 0) or 0))])
    if not out:
        return f"*no serve rows in {path}*"
    table = markdown_table(
        ["serve path", "tokens/s", "p50 token ms", "p95 token ms",
         "peak cache bytes", "mismatches"], out)
    if "engine" in by_mode and "sequential" in by_mode and \
            by_mode["sequential"].get("tokens_per_s"):
        ratio = (by_mode["engine"].get("tokens_per_s", 0.0)
                 / by_mode["sequential"]["tokens_per_s"])
        table += (f"\n\ncontinuous batching vs sequential: "
                  f"**{ratio:.2f}x** tokens/s (gate: >= 1.5x)")
    return table


def kernels_table(path: str) -> str:
    """§Kernels table from a ``BENCH_kernels.json`` (benchmarks.run --only
    kernels): the fused-chain loop bench (analytic bytes-moved per step +
    trajectory parity) and the per-kernel interpret-mode microbench rows.
    The gate line compares the fused chain's HBM byte model against the
    unfused stage-by-stage pass count — roofline-anchored, not wall-clock
    (DESIGN.md §14).  Tolerates an absent/empty file."""
    if not os.path.exists(path):
        return f"*no kernels bench found at {path}*"
    try:
        rows = json.load(open(path))
    except (OSError, json.JSONDecodeError):
        return f"*unreadable kernels bench at {path}*"
    by_mode = {}
    out = []
    for r in rows:
        name = r.get("name", "")
        if not name.startswith("kernels/"):
            continue
        if "bytes_moved_per_step" in r:
            by_mode[name.rsplit("/", 1)[-1]] = r
        out.append([
            name, f"{r.get('us_per_call', 0.0):.1f}",
            str(int(r["bytes_moved_per_step"]))
            if "bytes_moved_per_step" in r else "-",
            str(int(r["mismatches"])) if "mismatches" in r else "-",
            f"{r['jnp_ref_us']:.1f}" if "jnp_ref_us" in r else "-"])
    if not out:
        return f"*no kernels rows in {path}*"
    table = markdown_table(
        ["kernel path", "us/call", "bytes moved/step", "mismatches",
         "jnp ref us"], out)
    if "fused" in by_mode and "unfused" in by_mode and \
            by_mode["unfused"].get("bytes_moved_per_step"):
        ratio = (by_mode["fused"]["bytes_moved_per_step"]
                 / by_mode["unfused"]["bytes_moved_per_step"])
        mism = int(by_mode["fused"].get("mismatches", 0) or 0)
        table += (f"\n\nfused vs unfused bytes-moved: **{ratio:.3f}x** "
                  f"(gate: <= 0.5x); trajectory parity mismatches: "
                  f"**{mism}** (gate: == 0)")
    return table


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--what", default="kernels",
                    choices=["serve", "kernels", "all"])
    ap.add_argument("--bench-serve", default="BENCH_serve.json",
                    metavar="PATH", help="serve bench JSON for --what "
                    "serve/all (absent file renders a placeholder)")
    ap.add_argument("--bench-kernels", default="BENCH_kernels.json",
                    metavar="PATH", help="kernels bench JSON for --what "
                    "kernels/all (absent file renders a placeholder)")
    ap.add_argument("--out", default=None,
                    help="write the rendered markdown here instead of stdout")
    args = ap.parse_args(argv)
    parts = []
    if args.what in ("serve", "all"):
        parts.append(serve_table(args.bench_serve))
    if args.what in ("kernels", "all"):
        parts.append(kernels_table(args.bench_kernels))
    text = "\n\n".join(parts)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
        print(f"wrote {args.out}")
    else:
        print(text)


if __name__ == "__main__":
    main()

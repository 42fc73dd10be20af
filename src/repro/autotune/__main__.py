"""Autotuner CLI: tune a shape list, print the model-validation table,
persist the winners to the tuned-config cache.

  PYTHONPATH=src python -m repro.autotune --shapes n=512:bw=32 --backend ref
  PYTHONPATH=src python -m repro.autotune \\
      --shapes n=256:bw=16,n=512:bw=32 --backend ref --top-k 3 --iters 2

Each ``--shapes`` item is ``n=<int>:bw=<int>``.  The winning
``(tw, max_batch)`` per shape is merged into the cache at
``--cache`` / ``$REPRO_AUTOTUNE_CACHE`` / the XDG default, keyed by
``(device_kind, n, bw, dtype, compute_uv, backend)`` — exactly the key
``PipelineConfig.resolve(autotune=True)`` then looks up.  ``--no-store``
runs the search and table without touching the cache.
"""

from __future__ import annotations

import argparse
import sys

import jax.numpy as jnp

from repro.autotune import cache as cache_mod
from repro.autotune import model as model_mod
from repro.autotune import search as search_mod
from repro.kernels import ops


def parse_shapes(spec: str) -> list[tuple[int, int]]:
    """"n=512:bw=32,n=256:bw=16" -> [(512, 32), (256, 16)]."""
    shapes = []
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        fields = dict(kv.split("=", 1) for kv in item.split(":"))
        try:
            shapes.append((int(fields["n"]), int(fields["bw"])))
        except (KeyError, ValueError) as e:
            raise SystemExit(f"bad --shapes item {item!r} "
                             f"(want n=<int>:bw=<int>): {e}")
    if not shapes:
        raise SystemExit("--shapes parsed to nothing")
    return shapes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.autotune",
        description="Tune (tw, batch) per shape; persist the winners.")
    ap.add_argument("--shapes", required=True,
                    help="comma list of n=<int>:bw=<int> items")
    ap.add_argument("--backend", default="auto",
                    help="kernel registry key (auto/ref/pallas)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--compute-uv", action="store_true",
                    help="tune the tape-mode (full SVD) pipeline")
    ap.add_argument("--top-k", type=int, default=3,
                    help="measured candidates per shape (model-ranked)")
    ap.add_argument("--batches", default="1",
                    help="comma list of batch sizes to include in the grid")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--iters", type=int, default=1,
                    help="timed repetitions per candidate (median)")
    ap.add_argument("--cache", default="",
                    help=f"cache path (default: ${cache_mod.ENV_VAR} or "
                         f"{cache_mod.cache_path()})")
    ap.add_argument("--no-store", action="store_true",
                    help="print the table only; do not write the cache")
    ap.add_argument("--fused-crossover", action="store_true",
                    help="instead of the (tw, batch) grid, measure the "
                         "fused-vs-staged crossover per --shapes bw "
                         "(DESIGN.md §13) and persist fused_n_max")
    ap.add_argument("--stage3-crossover", action="store_true",
                    help="instead of the (tw, batch) grid, measure the "
                         "stage-3 bisect-vs-dc crossover up to the largest "
                         "--shapes n (DESIGN.md §14) and persist dc_n_min")
    ap.add_argument("--trace-jsonl", default="", metavar="PATH",
                    help="export measurement spans (warmup vs timed reps, "
                         "compile attribution) to PATH as JSONL "
                         "(repro.obs; DESIGN.md §16)")
    args = ap.parse_args(argv)

    if args.trace_jsonl:
        from repro import obs
        obs.install(obs.Tracer("autotune", jsonl=args.trace_jsonl))
        print(f"# tracing measurement spans to {args.trace_jsonl}",
              flush=True)

    dtype = jnp.dtype(args.dtype)
    if dtype.itemsize == 8:
        # Without x64, float64 measurement arrays silently degrade to
        # fp32 — timings for the wrong precision, and the crossover
        # searches' sigma-agreement column reads ~1e-5 instead of ~1e-16.
        import jax
        jax.config.update("jax_enable_x64", True)
    backend, _ = ops.resolve_backend(args.backend, dtype=dtype)
    try:
        batches = tuple(sorted({int(b) for b in args.batches.split(",")
                                if b.strip()}))
    except ValueError as e:
        raise SystemExit(f"bad --batches {args.batches!r} "
                         f"(want a comma list of ints): {e}")
    if not batches or min(batches) < 1:
        raise SystemExit(f"bad --batches {args.batches!r}: need at least "
                         f"one batch size >= 1")
    path = args.cache or None
    kind = model_mod.device_kind()
    prof = model_mod.profile_for(kind)
    print(f"# autotune device={kind} profile={prof.device_kind} "
          f"backend={backend} dtype={dtype.name}", flush=True)

    if args.fused_crossover:
        # One sweep per distinct bw; the shape's n caps the sweep.  The
        # result is stored under BOTH the bw-specific and the device-wide
        # crossover key (lookup_crossover prefers the specific one).
        caps: dict[int, int] = {}
        for n, bw in parse_shapes(args.shapes):
            caps[bw] = max(caps.get(bw, 0), n)
        for bw, n_cap in sorted(caps.items()):
            ns = tuple(x for x in (16, 32, 64, 128, 256, 384, 512)
                       if x <= n_cap) or (n_cap,)
            res = search_mod.search_fused_crossover(
                bw, dtype=dtype, compute_uv=args.compute_uv, ns=ns,
                batch=max(batches), profile=prof, warmup=args.warmup,
                iters=args.iters)
            print(res.table(), flush=True)
            if args.no_store:
                continue
            for key_bw in (bw, None):
                dest = cache_mod.store_crossover(
                    res.to_entry(), device_kind=kind, dtype=dtype.name,
                    compute_uv=args.compute_uv, bw=key_bw, path=path)
            print(f"# cached fused_n_max={res.fused_n_max} -> {dest}",
                  flush=True)
        return 0

    if args.stage3_crossover:
        # One sweep, capped by the largest --shapes n; bw is irrelevant
        # (stage 3 never sees the band).  The key is (device, dtype, uv).
        n_cap = max(n for n, _ in parse_shapes(args.shapes))
        ns = tuple(x for x in (256, 512, 1024, 2048, 4096, 8192)
                   if x <= n_cap) or (n_cap,)
        res = search_mod.search_stage3_crossover(
            dtype=dtype, compute_uv=args.compute_uv, ns=ns,
            batch=max(batches), profile=prof, warmup=args.warmup,
            iters=args.iters)
        print(res.table(), flush=True)
        if not args.no_store:
            dest = cache_mod.store_stage3(
                res.to_entry(), device_kind=kind, dtype=dtype.name,
                compute_uv=args.compute_uv, path=path)
            print(f"# cached dc_n_min={res.dc_n_min} -> {dest}", flush=True)
        return 0

    for n, bw in parse_shapes(args.shapes):
        res = search_mod.search(n, bw, dtype=dtype, backend=backend,
                                compute_uv=args.compute_uv,
                                top_k=args.top_k, batches=batches,
                                profile=prof, warmup=args.warmup,
                                iters=args.iters)
        print(res.table(), flush=True)
        if args.no_store:
            continue
        dest = cache_mod.store(res.to_entry(), device_kind=kind, n=n, bw=bw,
                               dtype=dtype.name, compute_uv=args.compute_uv,
                               backend=backend, path=path)
        print(f"# cached {res.best.label()} -> {dest}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

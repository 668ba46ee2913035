"""On-chip serving benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the served path (``ServeEngine`` over the request scheduler, the
fabric view, the page tables and prefix trie, ``PagedDecoder`` and the
Pallas paged kernels) for the cell's configuration, fills it as the cell's
traffic needs, drives ``ServeEngine.step`` for ``--seconds``, checks what
it served against the configuration's plain float32 reference, and prints
one JSON line last: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones
from a profiler trace of the window), ``device`` and ``checks`` (each
number compared, with its limit; also the last lines on standard error).

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for. Compiled programs are kept where
``repro.launch.serve.enable_compile_cache`` puts them:
``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` in the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH_DIR), str(CHECKOUT / "src")]

    import jax
    from chipbench import harness, roofline
    from repro.launch.serve import enable_compile_cache

    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cell = harness.cell_of(bench, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 1
    peaks = roofline.peaks(devs[0].device_kind)
    print(f"compile cache: {enable_compile_cache()}")
    out = harness.run(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, peaks=peaks,
                      log=lambda msg: print(msg, flush=True))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

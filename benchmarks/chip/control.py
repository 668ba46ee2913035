"""Readings that the limits of ``correct`` are set from (``limits/``).

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in one process: one run of the cell at its own size and
load, as ``run.py`` makes it, whose sample of served tokens the reference
then reads twice: with the float8 control (the reference with every weight
matrix rounded to float8) in the program's place, judged as ``correct``
judges the program, so that ``correct`` comes out false; and the program's
own widest logit gap (``program_gap``). Prints one JSON line per seed,
each reading beside the cell's limit. Needs the cell's chips.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import BENCH_DIR, CHECKOUT  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH_DIR), str(CHECKOUT / "src")]

    import jax
    from chipbench import harness, roofline
    from repro.launch.serve import enable_compile_cache

    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cell = harness.cell_of(bench, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print("control.py needs the cell's TPU chips", file=sys.stderr)
        return 1
    peaks = roofline.peaks(devs[0].device_kind)
    enable_compile_cache()
    t = T_START
    for seed in args.seeds:
        out = harness.run(bench, args.workload, seed, args.seconds, False,
                          t_start=t, peaks=peaks, control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "program_gap": out["program_gap"],
                          "metrics": out["metrics"],
                          "device": out["device"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""chipbench: one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: load, warm up, measure for --seconds, check the outputs,
print the result as the last line of stdout, exit 0. Without the chips
the cell names it exits non-zero and prints no result. See
chipbench/README.md.
"""
import time
_T0 = time.perf_counter()          # process start, as near as Python gets

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="another BENCHMARK.json (rehearsals and tests)")
    ap.add_argument("--rehearse", action="store_true",
                    help="by hand: allow a run without the chip, at the "
                    "tiny sizes of chipbench/tests/rehearsal; every line "
                    "says so and no number of it is a device number")
    ns = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        raise SystemExit("chipbench: the program under test (mxnet_tpu/) "
                         f"is not in {ROOT}; there is nothing to measure")
    from chipbench import common, manifest
    common.set_caches()
    root = ROOT
    if ns.manifest:
        root = os.path.dirname(os.path.abspath(ns.manifest))
    cell = manifest.resolve(manifest.load(ns.manifest, root=root),
                            ns.workload, root=root)
    # a configuration may state settings of its deployment ("env"); they
    # are in place before the program is imported
    os.environ.update(cell.config.get("env", {}))
    device = common.require_devices(cell.chips, rehearse=ns.rehearse)
    common.say("start", workload=cell.name, seed=ns.seed,
               seconds=ns.seconds, trace=ns.trace, rehearsal=ns.rehearse,
               **device)

    kind = cell.config["kind"]
    if kind == "fit":
        from chipbench import fit_runner as runner
    elif kind == "serve":
        from chipbench import serve_runner as runner
    else:
        raise SystemExit(f"chipbench: configuration kind {kind!r}: there "
                         "are runners for 'fit' and 'serve'")
    runner.run(cell, seed=ns.seed, seconds=ns.seconds, trace=bool(ns.trace),
               device=device, t_start=_T0, rehearse=ns.rehearse)


if __name__ == "__main__":
    main()

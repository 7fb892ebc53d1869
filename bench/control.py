"""The control of a cell's comparison: the reference put in the
program's place with the guarantee the configuration states broken (a
narrower dedup key, ``control.fp_bits`` of the configuration), compared
with the exact reference at the cell's own size.  It must come out as
not correct.  The benchmark's own runs never run it.

    python bench/control.py --workload <cell> --seed <n> [--seed <n> ...]
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import compare, manifest, reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    cell = manifest.cell(args.workload, manifest.load())
    conf = cell.conf
    so = reference.build(os.path.join(manifest.ROOT, ".bench_cache",
                                      "reference"))
    depth = int(conf["max_depth"])
    exact = reference.check(so, conf["model"], depth)
    bad = False
    for seed in args.seed:
        # the seed varies nothing in a model check (traffic file)
        ctl = reference.check(so, conf["model"], depth,
                              fp_bits=int(conf["control"]["fp_bits"]))
        out = compare.compare([ctl], exact)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "fp_bits": conf["control"]["fp_bits"],
                          "correct": compare.ok(out), "compared": out,
                          "control_s": ctl.seconds}), flush=True)
        bad |= compare.ok(out)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

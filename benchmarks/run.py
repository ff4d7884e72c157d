"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

resolves the cell's names to files (`harness/manifest.py`), hands them to the
driver its traffic mix names, and prints the result as the last line of its
output. It holds no cell's sizes and no branch on a cell: a configuration, a
mix, a driver or a per-layer reader is a file found by its name.

One process: it holds the chip from the driver's first JAX call to the end.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()  # the process's start, as near as Python gives it

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.harness import device  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402
from benchmarks.harness.record import Context  # noqa: E402
from benchmarks.harness.result import result_line  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    return p.parse_args(argv)


def main(argv=None, t0: float = _T0) -> int:
    args = parse(argv)
    manifest = Manifest(Path(args.manifest))
    cell = manifest.workload(args.workload)
    try:
        devices = device.require(int(cell["chips"]))
    except device.NoAccelerator as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    traffic = manifest.load_json(f"traffic/{cell['traffic']}.json")
    driver = manifest.load_module(f"drivers/{traffic['driver']}.py")
    record = driver.run(
        Context(
            manifest=manifest,
            config=manifest.config(cell["config"]),
            traffic=traffic,
            seed=args.seed,
            seconds=args.seconds,
            trace=bool(args.trace),
            t0=t0,
            devices=devices,
        )
    )
    for key, value in record.notes.items():
        print(f"# {key}: {json.dumps(value, default=str)}")
    print(result_line(manifest, cell, record, traced=bool(args.trace)))
    for name, number in record.compared.items():
        print(f"compared {name}: {number['value']!r} limit {number['limit']!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

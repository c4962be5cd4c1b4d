"""One run of one cell of ``BENCHMARK.json``.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. One process, one cell, and as the last line
of standard output one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (``breakdown`` too when traced).
Everything else a run has to say goes on earlier lines.

Nothing here knows a cell, a configuration, a mix or a metric by name.
The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration is the ``file`` of the entry of ``configs`` it names (whose
``family`` names a module of ``benchmark/families/``); its traffic is
``benchmark/traffic/<traffic>.json`` (whose ``job`` names a module of
``benchmark/jobs/``); a per-layer metric is read by
``benchmark/layer_metrics/<name>.py``. A new cell, model, mix or metric
is new files and new entries.
"""

import time

PROCESS_START = time.perf_counter()  # before the heavy imports: setup_s

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def reported_in(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        sys.exit(f"benchmark: no workload {args.workload!r} in "
                 f"BENCHMARK.json (has: {', '.join(sorted(cells))})")
    cell = cells[args.workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    job = importlib.import_module(f"benchmark.jobs.{traffic['job']}")
    result = job.run(cell, config, traffic, args, PROCESS_START)

    if args.trace:
        values, wanted = {}, bench["per_layer"]
        inputs = result.get("layer_inputs")  # none if a step raised
        for metric in wanted if inputs else ():
            if not reported_in(metric, cell["name"]):
                continue
            reader = importlib.import_module(
                f"benchmark.layer_metrics.{metric['name']}")
            value = reader.read(inputs)
            if value is not None:
                values[metric["name"]] = value
    else:
        values, wanted = result["values"], bench["end_to_end"]
    units = {m["name"]: m["unit"] for m in wanted
             if reported_in(m, cell["name"])}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"],
            "metrics": {name: {"value": values[name], "unit": unit}
                        for name, unit in units.items() if name in values},
            "device": result["device"]}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

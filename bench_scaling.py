"""Weak-scaling sweep over REAL multi-process worlds: the paper's
acceptance curve as a checked-in artifact.

The reference's published claim is 90% scaling efficiency for
ResNet-101 at 512 GPUs (docs/benchmarks.rst:12-14): efficiency =
(img/s at N chips) / (N x img/s at 1 chip), per-chip batch held
constant. This driver measures that curve across a sweep of *worlds*
— each ``PxD`` world is P real ``jax.distributed`` processes x D
local devices forming ONE logical ``(dcn, data)`` mesh via the
process-mesh subsystem (``horovod_tpu/cluster/``, docs/SCALING.md) —
and emits one JSON document per sweep:

* per-world median step time, img/s, img/s/chip and the
  **scaling-efficiency curve** against the sweep's smallest world;
* per-world **goodput breakdown** aggregated across all P processes
  from their goodput-ledger dumps (gate: <= 2% unattributed per
  world);
* per-world **compiled-collective bytes per mesh axis** — the DCN
  tier priced separately from ICI straight from the compiled HLO's
  replica groups (``gspmd.collective_axis_bytes_from_hlo``).

Checked in as ``SCALING_r<NN>.json``, diffed by ``bench.py --compare``
(efficiency is higher-is-better in telemetry/trend.py), so a scaling
regression bends a curve instead of hiding in an anecdote.

CPU stand-in (this is how the checked-in rounds are produced — CPU
timings are NOT meaningful TPU efficiency numbers, the curve's
*structure* and byte ledger are the regression anchors)::

    python bench_scaling.py --model resnet18 --batch-size 2 \
        --image-size 32 --worlds 1x1,1x2,2x1,2x2 --out SCALING_r01.json

This is a CPU harness by construction: every rank it spawns is pinned
to the CPU (``JAX_PLATFORMS=cpu`` in ``_world_env``), so its children
never contend for a chip and nothing it prints is a device number.
"""

import argparse
import json
import os
import shlex
import socket
import subprocess
import sys
import tempfile
import time

WORLD_TIMEOUT_S = 600

BASELINE_EFFICIENCY = {  # reference docs/benchmarks.rst:12-14, 512 GPUs
    "resnet101": 0.90, "resnet50": 0.90, "vgg16": 0.68}


def parse_worlds(spec):
    """``"1x1,1x2,2x2"`` -> ``[(1, 1), (1, 2), (2, 2)]`` (processes x
    local devices per process)."""
    worlds = []
    for tok in spec.split(","):
        tok = tok.strip().lower()
        if not tok:
            continue
        try:
            procs, local = tok.split("x")
            worlds.append((int(procs), int(local)))
        except ValueError:
            raise SystemExit(
                f"bench_scaling: bad world {tok!r} (want PROCSxDEVICES, "
                "e.g. 2x2)")
        if worlds[-1][0] < 1 or worlds[-1][1] < 1:
            raise SystemExit(
                f"bench_scaling: bad world {tok!r}: processes and "
                "devices must both be >= 1")
    if not worlds:
        raise SystemExit("bench_scaling: --worlds is empty")
    return worlds


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _strip_forced_device_count(flags):
    return " ".join(f for f in flags.split()
                    if "xla_force_host_platform_device_count" not in f)


def _world_env(rank, procs, local_devices, coord, out_dir):
    env = dict(os.environ)
    env.update({
        "HOROVOD_RANK": str(rank),
        "HOROVOD_SIZE": str(procs),
        "HOROVOD_LOCAL_RANK": str(rank),
        "HOROVOD_LOCAL_SIZE": str(procs),
        "HOROVOD_CROSS_RANK": "0",
        "HOROVOD_CROSS_SIZE": "1",
        "HOROVOD_SPMD_PROCS": str(procs),
        "HOROVOD_SPMD_LOCAL_DEVICES": str(local_devices),
        "HOROVOD_FLIGHTREC": "1",  # goodput dumps even for 1-proc worlds
        "HOROVOD_FLIGHTREC_DIR": out_dir,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (_strip_forced_device_count(
            env.get("XLA_FLAGS", ""))
            + f" --xla_force_host_platform_device_count={local_devices}"
        ).strip(),
    })
    if coord:
        env["HOROVOD_COORDINATOR_ADDR"] = coord
    else:
        env.pop("HOROVOD_COORDINATOR_ADDR", None)
    return env


def run_world(procs, local_devices, worker_args, out_dir,
              timeout=WORLD_TIMEOUT_S):
    """Launch one ``procs x local_devices`` world (every rank a real
    jax.distributed process of one coordinator) and wait. Raises on any
    nonzero rank."""
    coord = f"127.0.0.1:{_free_port()}" if procs > 1 else None
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--result-dir", out_dir] + worker_args
    children = []
    for rank in range(procs):
        log = open(os.path.join(out_dir, f"rank.{rank}.log"), "wb")
        children.append((rank, subprocess.Popen(
            cmd, env=_world_env(rank, procs, local_devices, coord,
                                out_dir),
            stdout=log, stderr=subprocess.STDOUT), log))
    deadline = time.monotonic() + timeout
    failed = []
    try:
        for rank, proc, _log in children:
            left = max(1.0, deadline - time.monotonic())
            try:
                rc = proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                proc.kill()
                rc = proc.wait()
                failed.append((rank, "timeout"))
                continue
            if rc != 0:
                failed.append((rank, f"exit {rc}"))
    finally:
        for _rank, proc, log in children:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            log.close()
    if failed:
        tails = []
        for rank, why in failed:
            path = os.path.join(out_dir, f"rank.{rank}.log")
            with open(path, "rb") as f:
                tail = f.read()[-2000:].decode("utf-8", "replace")
            tails.append(f"--- rank {rank} ({why}) ---\n{tail}")
        raise RuntimeError(
            f"world {procs}x{local_devices} failed: " + "\n".join(tails))


# ---------------------------------------------------------------------------
# Worker: one process of one world. Measures the GSPMD step on the
# process mesh, then writes world_result.rank<R>.json; the goodput dump
# lands via the normal shutdown path.
# ---------------------------------------------------------------------------

def worker(args):
    import jax
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.cluster import mesh_tiers
    from horovod_tpu.utils.benchmarks import (make_model, synthetic_batch,
                                              timed_throughput)

    hvd.init()
    mesh = hvd.mesh()
    chips = int(jax.device_count())
    model = make_model(args.model)
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    images, labels = synthetic_batch(args.batch_size * chips,
                                     args.image_size)
    state = training.create_train_state(model, tx, jax.random.PRNGKey(0),
                                        images[:1])
    step = training.make_train_step(model, tx, mesh=mesh, donate=True,
                                    spmd=True)
    ips, dt = timed_throughput(step, state, images, labels,
                               args.num_warmup, args.num_iters)
    result = {
        "rank": int(jax.process_index()),
        "procs": int(jax.process_count()),
        "local_devices": len(jax.local_devices()),
        "chips": chips,
        "global_batch": int(args.batch_size * chips),
        "img_per_sec": round(float(ips), 2),
        "step_ms_median": round(1e3 * dt / args.num_iters, 3),
        "mesh_tiers": mesh_tiers(mesh),
        "collective_bytes_per_axis": step.compiled_axis_collectives,
    }
    path = os.path.join(
        args.result_dir, f"world_result.rank{result['rank']}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    hvd.shutdown()  # writes goodput.rank<R>.json next to the result


# ---------------------------------------------------------------------------
# Driver: sweep the worlds, aggregate, emit the curve.
# ---------------------------------------------------------------------------

def _world_entry(procs, local, out_dir):
    from horovod_tpu.telemetry import report as report_mod

    with open(os.path.join(out_dir, "world_result.rank0.json")) as f:
        res = json.load(f)
    dumps, skipped = report_mod.load_dumps(out_dir)
    if sorted(dumps) != list(range(procs)):
        raise RuntimeError(
            f"world {procs}x{local}: goodput dumps cover ranks "
            f"{sorted(dumps)}, want 0..{procs - 1} (skipped={skipped})")
    goodput = report_mod.aggregate(dumps)
    fleet = goodput["fleet"]
    unattributed_frac = (fleet["unattributed_seconds"]
                         / max(fleet["wall_seconds"], 1e-9))
    return {
        "world": f"{procs}x{local}",
        "procs": procs,
        "local_devices": local,
        "chips": res["chips"],
        "global_batch": res["global_batch"],
        "step_ms_median": res["step_ms_median"],
        "img_per_sec": res["img_per_sec"],
        "img_per_sec_per_chip": round(
            res["img_per_sec"] / res["chips"], 2),
        "mesh_tiers": res["mesh_tiers"],
        "collective_bytes_per_axis": res["collective_bytes_per_axis"],
        "goodput": {
            "ratio": round(fleet["goodput_ratio"], 4),
            "unattributed_frac": round(unattributed_frac, 4),
            "dominant_sink": fleet["dominant_sink"],
            "ranks": {
                str(r): {
                    "goodput_ratio": round(i["goodput_ratio"], 4),
                    "unattributed_seconds": round(
                        i["unattributed_seconds"], 4),
                    "wall_seconds": round(i["wall_seconds"], 4),
                }
                for r, i in goodput["ranks"].items()},
        },
    }


def drive(args):
    worlds = parse_worlds(args.worlds)
    passthrough = ["--model", args.model,
                   "--batch-size", str(args.batch_size),
                   "--image-size", str(args.image_size),
                   "--num-warmup", str(args.num_warmup),
                   "--num-iters", str(args.num_iters)]
    entries = []
    for procs, local in worlds:
        out_dir = tempfile.mkdtemp(
            prefix=f"scaling_{procs}x{local}_", dir=args.work_dir)
        print(f"bench_scaling: world {procs}x{local} "
              f"({procs * local} chips) ...", file=sys.stderr)
        run_world(procs, local, passthrough, out_dir,
                  timeout=args.world_timeout)
        entry = _world_entry(procs, local, out_dir)
        entries.append(entry)
        print(f"bench_scaling:   {entry['img_per_sec']} img/s "
              f"({entry['img_per_sec_per_chip']}/chip), "
              f"unattributed {entry['goodput']['unattributed_frac']:.2%}",
              file=sys.stderr)

    base = entries[0]
    curve = {}
    for e in entries:
        eff = (e["img_per_sec_per_chip"]
               / max(base["img_per_sec_per_chip"], 1e-9))
        e["efficiency"] = round(eff, 4)
        curve[e["world"]] = e["efficiency"]

    ref = BASELINE_EFFICIENCY.get(args.model)
    last = entries[-1]
    doc = {
        "bench": "scaling",
        "model": args.model,
        "per_chip_batch": args.batch_size,
        "image_size": args.image_size,
        "num_iters": args.num_iters,
        "platform": "cpu" if os.environ.get(
            "JAX_PLATFORMS", "cpu") == "cpu" else os.environ.get(
            "JAX_PLATFORMS"),
        "baseline_world": base["world"],
        "worlds": entries,
        "efficiency_curve": curve,
        "metric": (f"{args.model}_weak_scaling_efficiency_"
                   f"{last['chips']}chips"),
        "value": last["efficiency"],
        "unit": "fraction",
        "vs_baseline": (round(last["efficiency"] / ref, 3)
                        if ref else None),
        "cmd": "python bench_scaling.py " + " ".join(
            shlex.quote(a) for a in sys.argv[1:]),
    }
    bad = [e["world"] for e in entries
           if e["goodput"]["unattributed_frac"] > 0.02]
    if bad:
        doc["unattributed_violations"] = bad
    print(json.dumps(doc if args.verbose_json else {
        k: doc[k] for k in ("metric", "value", "unit", "vs_baseline",
                            "efficiency_curve", "baseline_world")}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"bench_scaling: wrote {args.out}", file=sys.stderr)
    if bad:
        print(f"bench_scaling: UNATTRIBUTED > 2% in worlds {bad}",
              file=sys.stderr)
        return 1
    return 0


def main():
    ap = argparse.ArgumentParser(
        description="Weak-scaling sweep over multi-process worlds. CPU "
                    "harness: every rank it spawns is pinned to "
                    "JAX_PLATFORMS=cpu, so it never touches or contends "
                    "for a chip and its times are not device numbers.")
    ap.add_argument("--model", default="resnet101",
                    choices=["resnet18", "resnet50", "resnet101", "vgg16"])
    ap.add_argument("--batch-size", type=int, default=64,
                    help="PER-CHIP batch (held constant: weak scaling)")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-warmup", type=int, default=3)
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--worlds", default="1x1,1x2,2x1,2x2",
                    help="comma-separated PROCSxDEVICES worlds, smallest "
                         "first (the first world is the efficiency "
                         "baseline)")
    ap.add_argument("--out", default=None,
                    help="also write the full sweep document here "
                         "(SCALING_r<NN>.json)")
    ap.add_argument("--work-dir", default=None,
                    help="where per-world scratch dirs live (default: "
                         "system temp)")
    ap.add_argument("--world-timeout", type=int, default=WORLD_TIMEOUT_S)
    ap.add_argument("--verbose-json", action="store_true",
                    help="print the full document on stdout instead of "
                         "the one-line summary")
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one world rank
    ap.add_argument("--result-dir", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker:
        if not args.result_dir:
            raise SystemExit("bench_scaling: --worker needs --result-dir")
        return worker(args) or 0
    return drive(args)


if __name__ == "__main__":
    sys.exit(main())

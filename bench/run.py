"""The benchmark of the trainer path: ray_tpu.init() -> raylet lease of the
cell's chips -> JaxTrainer worker -> TrainStep.init/step -> models/, ops/.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of BENCHMARK.json's "workloads"; its configuration, its
traffic mix and each per-layer metric are data files found by the names
given there (bench/configs, bench/traffic, bench/layer_metrics), and the
model family's code by the name the configuration gives (bench/families).
No cell, configuration or metric is named in this file.

This process never touches JAX: the chips belong to the train worker. It
starts the runtime, hands bench/worker.py's loop to JaxTrainer, reads what
came back, stops everything, and prints one JSON line last. Without a TPU,
or with fewer chips than the cell asks for, it fails and prints no result.

--rehearse runs the same command end to end on CPU devices at the tiny
sizes the data files carry under "rehearsal": it proves paths and
arguments, measures nothing and never prints a result line.
"""

import time

T_START = time.time()  # set-up is counted from here

import argparse
import glob
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

# |system - reference| / |reference| on the first batch: the plain float32
# reference against the bf16 system (bf16 inputs to every matmul, float32
# accumulation, float32 logits and loss). Over every chip run of PR 24 (three
# cells, 60 runs, 7 seeds) the loss agreed within 1.4e-5 and the gradient norm
# within 6.9e-4; the bounds are 7 and 4 times that. A matmul or kernel that
# accumulated in bf16 rounds each partial sum to 2**-9 = 2e-3 of its value, over
# 768 to 14,336 terms: percents on the gradient norm, far outside.
TOLERANCE = {"loss": 1e-4, "grad_norm": 3e-3}


def _fail(msg, code=3):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def _load_cell(name, rehearse):
    from bench import traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        _fail(f"no workload {name!r} in BENCHMARK.json; there are {sorted(cells)}", 2)
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        sizes = json.load(f)
    if rehearse:
        sizes.update(sizes.get("rehearsal", {}))

    def in_cell(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell, "sizes": sizes,
        "traffic": traffic.load(cell["traffic"], rehearse),
        "end_to_end": [m for m in bench["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in bench["per_layer"] if in_cell(m)],
    }


def _peaks(kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        _fail(f"no peaks known for device kind {kind!r}; bench/peaks.json has {sorted(table)}")
    return table[kind]


def _loop(spec):
    """Pickled by value into the train worker, which finds the rest of the
    benchmark in this checkout."""
    import sys

    if spec["root"] not in sys.path:
        sys.path.insert(0, spec["root"])
    from bench import worker

    worker.loop(spec)


def _left_running(session_dir):
    """Processes of this run's session that are still there (the runtime's
    daemons and workers name their session directory on the command line)."""
    left = []
    for path in glob.glob("/proc/[0-9]*/cmdline"):
        try:
            with open(path, "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if session_dir in cmd:
            left.append(f"{path.split('/')[2]} {cmd[:160]}")
    return left


def _print_worker_errors(session_dir, tail=6000):
    for path in sorted(glob.glob(os.path.join(session_dir, "logs", "worker-*.err"))):
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - tail))
            text = f.read().decode(errors="replace").strip()
        if text:
            print(f"--- tail of {path}\n{text}", file=sys.stderr, flush=True)


def _intervals(rows, phase):
    """Seconds between completions, for the steps that completed in a phase
    (the first one's interval starts at the completion before it)."""
    return [b["t_done"] - a["t_done"] for a, b in zip(rows, rows[1:])
            if b["phase"] == phase]


def _step_log(out_dir):
    """The per-step log, and how even it is in one line."""
    with open(os.path.join(out_dir, "steps.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    summary = {}
    for phase in ("warmup", "window"):
        gaps = _intervals(rows, phase)
        if gaps:
            med = statistics.median(gaps)
            summary[phase] = {
                "steps": len(gaps), "median_s": med, "max_s": max(gaps),
                "slower_than_1.2x_median": sum(g > 1.2 * med for g in gaps)}
    return rows, summary


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe-trace", action="store_true",
                    help="with --trace 1: also write what the raw trace holds")
    args = ap.parse_args()

    loaded = _load_cell(args.workload, args.rehearse)
    cell, sizes, mix = loaded["cell"], loaded["sizes"], loaded["traffic"]
    chips = cell["chips"]
    try:
        import ray_tpu
    except ImportError as e:
        _fail(f"this checkout has no ray_tpu to measure: {e}")
    if os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__))) != ROOT:
        _fail(f"ray_tpu was imported from {ray_tpu.__file__}, not from this checkout")
    from ray_tpu import api
    from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

    out_dir = os.path.join(
        ROOT, ".bench_out", "rehearse" if args.rehearse else "runs",
        args.workload, f"seed{args.seed}_trace{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    # every program of the run goes to the persistent cache, the small ones too
    worker_env = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                  "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    t_init = time.time()
    if args.rehearse:
        ray_tpu.init(num_cpus=4, num_tpus=chips)
        worker_env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    else:
        ray_tpu.init()  # resources autodetected: the node must find its chips
    final, passed, left = None, False, []
    try:
        tpus = ray_tpu.cluster_resources().get("TPU", 0)
        if tpus < chips:
            _fail(f"node advertises TPU={tpus}, the cell needs {chips}")
        spec = {
            "root": ROOT, "out_dir": out_dir, "rehearse": args.rehearse,
            "chips": chips, "seed": args.seed, "seconds": args.seconds,
            "trace": args.trace, "describe_trace": args.describe_trace,
            "sizes": sizes, "traffic": mix,
        }
        result = JaxTrainer(
            _loop,
            train_loop_config=spec,
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker={"TPU": chips}),
            jax_config=JaxConfig(env=worker_env),
            run_config=RunConfig(
                name="bench", storage_path=os.path.join(out_dir, "results")),
        ).fit()
        final = result.metrics
        if not final.get("final"):
            _fail("the worker's last report is not its summary")
        jax = sys.modules.get("jax")
        if jax is not None and jax._src.xla_bridge.backends_are_initialized():
            _fail("the benchmark's own process initialized a JAX backend")
        passed = True
    finally:
        session_dir = api._local_node.session_dir
        if not passed:
            _print_worker_errors(session_dir)
        ray_tpu.shutdown()
        left = _left_running(session_dir)
    if left:
        _fail(f"shutdown left processes running: {left}")
    if os.path.exists(f"/proc/{final['worker_pid']}"):
        _fail(f"worker {final['worker_pid']} still holds its chips")

    device = final["device"]
    if device["count"] != chips:
        _fail(f"worker saw {device['count']} devices, the cell leases {chips}")
    rows, summary = _step_log(out_dir)
    print(json.dumps({"steps": summary, "out_dir": os.path.relpath(out_dir, ROOT)}),
          flush=True)
    print(json.dumps({"reference": final["reference"], "system": final["system"],
                      "rel_diff": final["rel_diff"], "tolerance": TOLERANCE}), flush=True)
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}), flush=True)
        return
    if device["platform"] != "tpu":
        _fail(f"ran on {device['platform']!r}, not a TPU")

    from bench import families, reducers, trace, traffic

    peaks = _peaks(device["kind"])
    window = [r for r in rows if r["phase"] == "window"]
    tokens = final["window_steps"] * traffic.tokens_per_step(mix)
    tokens_per_s = tokens / final["window_s"]
    flops = families.load(sizes["family"]).flops_per_token(sizes, mix["seq_len"])
    values = {
        "tokens_per_s": tokens_per_s,
        "mfu_pct": 100 * tokens_per_s * flops / (chips * peaks["bf16_flops_per_s"]),
        "setup_s": final["t_open_wall"] - T_START,
    }
    correct = (
        all(final["rel_diff"][k] <= TOLERANCE[k] for k in TOLERANCE)
        and final["nonfinite_in_window"] == 0
        and final["compiles_in_window"] == 0
        and final["window_steps"] > 0)
    line = {
        "correct": bool(correct),
        "attempted": final["window_steps"],
        "failed": final["nonfinite_in_window"],
        "device": {**device, "memory_peak_bytes": final["memory_peak_bytes"]},
    }
    if not args.trace:
        line["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                           for m in loaded["end_to_end"]}
    else:
        reduced = None
        if final["trace_file"]:
            with open(final["trace_file"]) as f:
                reduced = trace.Reduced(json.load(f))
        rec = {
            "spans": {s: [r[s + "_ms"] for r in window if s + "_ms" in r]
                      for s in ("input", "dispatch", "sync", "report")},
            "counters": {
                "compiles_in_window": final["compiles_in_window"],
                "worker_up_s": final["t_devices"] - t_init,
                "compile_s": final["t_compiled"] - final["t_ref"],
                "hbm_peak_gib": final["memory_peak_bytes"] / 2 ** 30,
            },
            "step_intervals_s": _intervals(rows, "window"),
            "trace": reduced, "peaks": peaks, "notes": {},
        }
        line["metrics"] = {}
        for m in loaded["per_layer"]:
            value = reducers.read(m["name"], rec)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if not reduced:
            _fail("the traced run holds no operation on a device")
        line["device"].update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        line["breakdown"] = {"device_ops": reduced.top_ops(), "idle_gaps": reduced.idle_gaps()}
        line["notes"] = rec["notes"]
    line["setup"] = {
        "runtime_and_lease_s": final["t_entry"] - T_START,
        "tpu_up_s": final["t_devices"] - final["t_entry"],
        "imports_s": final["t_imports"] - final["t_devices"],
        "weights_s": final["t_init"] - final["t_imports"],
        "reference_s": final["t_ref"] - final["t_init"],
        "first_step_s": final["t_compiled"] - final["t_ref"],
        "warmup_s": final["t_open_wall"] - final["t_compiled"],
        "compiled_first_step": final["compiled_first_step"],
    }
    line["memory_stats"] = final["memory_stats"]
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()

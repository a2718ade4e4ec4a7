"""The loop function the benchmark hands to JaxTrainer. It runs in the train
worker, the one process that holds the cell's chips; the benchmark's own
process never touches JAX. What it measures goes back through train.report
(the small per-step dict and the final summary) and, where it is long, into
files under the run's output directory.

One step of the loop is what a user's loop does: make the batch, shard it,
TrainStep.step, train.report. Completion is timed without draining the
queue: step k+1 is dispatched, then the loop blocks on step k's loss.
"""

import contextlib
import json
import math
import os
import shutil
import time


class Spans:
    """The loop's host spans: milliseconds per step, and the same span in
    the profiler's trace (bench.<name>) so idle gaps can be laid to it."""

    def __init__(self, annotate):
        self.annotate = annotate
        self.row = {}

    @contextlib.contextmanager
    def __call__(self, name):
        with self.annotate("bench." + name):
            t0 = time.perf_counter()
            yield
            self.row[name + "_ms"] = (time.perf_counter() - t0) * 1e3


def _reference_check(family, sizes, mix, ts, params, batch):
    """Loss and global gradient norm of the first batch by the plain float32
    reference, on the parameters TrainStep.init made. The chain rule is taken
    a layer at a time (jax.vjp of the family's `layer`) and some rows at a
    time, so that published widths fit beside the training state; the
    gradients are summed before the norm."""
    import jax
    import jax.numpy as jnp

    from bench import families

    names, outer = families.split_params(family, params, sizes)
    shard = ts.state_shardings["params"]
    outer_sh = {k: shard[k] for k in outer}

    def embed(o, idx):
        return family.embed(o, idx, sizes)

    def layer(x, blk):
        return family.layer(x, blk, sizes)

    def head(o, x, tgt):
        return family.head_loss(o, x, tgt, sizes)

    # activations and their cotangents stay split over the batch, as the
    # system's are; gradients come out sharded as their parameters
    acts = ts.batch_sharding
    fwd_embed = jax.jit(embed, out_shardings=acts)
    fwd_layer = jax.jit(layer, out_shardings=acts)
    bwd_head = jax.jit(
        lambda o, x, tgt: jax.value_and_grad(head, argnums=(0, 1))(o, x, tgt),
        out_shardings=(None, (outer_sh, acts)))
    bwd_layer = jax.jit(
        lambda x, blk, dy: jax.vjp(layer, x, blk)[1](dy),
        out_shardings=(acts, shard[names[0]]))
    bwd_embed = jax.jit(
        lambda o, idx, dx: jax.vjp(lambda o: embed(o, idx), o)[1](dx)[0],
        out_shardings=outer_sh)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    squares = jax.jit(lambda g: sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))

    rows = mix["reference_rows"]
    n = mix["batch"] // rows
    loss, grads = 0.0, None
    for i in range(n):
        part = {k: jax.device_put(v[i * rows:(i + 1) * rows], ts.batch_sharding)
                for k, v in batch.items()}
        xs = [fwd_embed(outer, part["idx"])]
        for name in names:
            xs.append(fwd_layer(xs[-1], params[name]))
        l, (d_outer, dx) = bwd_head(outer, xs.pop(), part["targets"])
        loss += float(l) / n
        g = {}
        for name in reversed(names):
            dx, g[name] = bwd_layer(xs.pop(), params[name], dx)
        g[""] = add(d_outer, bwd_embed(outer, part["idx"], dx))
        grads = g if grads is None else {k: add(grads[k], g[k]) for k in g}
    gnorm = math.sqrt(sum(float(squares(g)) for g in grads.values())) / n
    return {"loss": loss, "grad_norm": gnorm, "rows": rows * n}


def loop(spec):
    t_entry = time.time()
    import jax

    devs = jax.devices()
    t_devices = time.time()

    from bench import families, traffic
    from ray_tpu import train
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    rehearse, out_dir = spec["rehearse"], spec["out_dir"]
    platform, kind = devs[0].platform, devs[0].device_kind
    if not rehearse and platform != "tpu":
        raise RuntimeError(f"worker sees {platform!r}, not a TPU")
    if len(devs) != spec["chips"]:
        raise RuntimeError(f"worker leased {spec['chips']} chips sees {len(devs)} devices")

    sizes, mix = spec["sizes"], spec["traffic"]
    family = families.load(sizes["family"])
    cfg = family.build(sizes, sizes["compute_dtype"])
    t_imports = time.time()
    ts = TrainStep(cfg, make_mesh(sizes["mesh"], devices=devs))
    seed = spec["seed"]
    state = ts.init(jax.random.PRNGKey(seed & 0xFFFFFFFF))
    jax.block_until_ready(state)
    t_init = time.time()

    def host_batch(k):
        return traffic.make_batch(mix, sizes["vocab_size"], seed, k)

    # ---- correctness, outside the window: the plain reference on the first
    # batch, then the system's own first step (which also compiles it).
    first = host_batch(0)
    ref = _reference_check(family, sizes, mix, ts, state["params"], first)
    t_ref = time.time()
    cache_before = ts._step._cache_size()
    state, m = ts.step(state, ts.shard_batch(first))
    jax.block_until_ready(m)
    t_compiled = time.time()
    got = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"])}
    rel = {k: abs(got[k] - ref[k]) / abs(ref[k]) for k in got}

    # ---- the loop
    tracing = bool(spec["trace"])
    spans = Spans(jax.profiler.TraceAnnotation)
    seconds = float(spec["seconds"])
    rows, losses = [], []
    pending = None
    phase, k = "warmup", 1
    t_warm = time.perf_counter()
    t_open = t_close = t_open_wall = None
    done_in_phase = 0
    cache_at_open = None
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)  # an earlier run's, same seed
    stop = False
    while not stop:
        spans.row = row = {"k": k}
        with spans("input"):
            batch = ts.shard_batch(host_batch(k))
        with spans("dispatch"):
            state, m = ts.step(state, batch)
        if pending is not None:
            prow, pm = pending
            with spans("sync"):
                jax.block_until_ready(pm["loss"])
            now = time.perf_counter()
            prow["t_done"], prow["phase"] = now, phase
            losses.append(pm["loss"])
            rows.append(prow)
            done_in_phase += 1
            if phase == "warmup" and done_in_phase >= mix["warmup_steps"] \
                    and now - t_warm >= mix["warmup_seconds"]:
                phase, done_in_phase = "window", 0
                t_open, t_open_wall = now, time.time()
                cache_at_open = ts._step._cache_size()
            elif phase == "window" and now - t_open >= seconds:
                t_close = now
                cache_at_close = ts._step._cache_size()
                if tracing:
                    phase, done_in_phase = "trace", 0
                    # the step in flight was dispatched untraced: the traced
                    # window is cut to whole step programs afterwards
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir, profiler_options=opts)
                else:
                    stop = True
            elif phase == "trace" and done_in_phase >= mix["trace_steps"] + 1:
                stop = True
        pending = (row, m)
        with spans("report"):
            if k % mix["report_every"] == 0:
                train.report({"step": k, "phase": phase})
        k += 1
    prow, pm = pending
    jax.block_until_ready((state, pm))
    prow["t_done"], prow["phase"] = time.perf_counter(), "drain"
    losses.append(pm["loss"])
    rows.append(prow)
    trace_file = None
    if tracing:
        jax.profiler.stop_trace()
        from bench import trace as trace_mod

        xplane = trace_mod.newest_xplane(trace_dir)
        if xplane:
            trace_file = os.path.join(out_dir, "trace_events.json")
            with open(trace_file, "w") as f:
                json.dump(trace_mod.extract(xplane), f)
            if spec.get("describe_trace"):
                with open(os.path.join(out_dir, "trace_described.txt"), "w") as f:
                    f.write(trace_mod.describe(xplane))

    for row, loss in zip(rows, losses):
        row["loss"] = float(loss)
    with open(os.path.join(out_dir, "steps.jsonl"), "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")

    # The allocator books a program's temporaries apart from live buffers
    # (bytes_reserved, not bytes_in_use): a chip's peak is what the loop keeps
    # live plus the largest reservation, or the live peak where that is more.
    stats = [d.memory_stats() or {} for d in devs]
    peak = [max(s["peak_bytes_in_use"], s["bytes_in_use"] + s.get("peak_bytes_reserved", 0))
            for s in stats if "peak_bytes_in_use" in s]
    window = [r for r in rows if r["phase"] == "window"]
    train.report({
        "final": True,
        "device": {"platform": platform, "kind": kind, "count": len(devs)},
        "worker_pid": os.getpid(),
        "memory_peak_bytes": max(peak) if len(peak) == len(devs) else None,
        "memory_stats": {k: v for k, v in stats[0].items() if isinstance(v, (int, float))},
        "t_entry": t_entry, "t_devices": t_devices, "t_imports": t_imports, "t_init": t_init,
        "t_ref": t_ref, "t_compiled": t_compiled, "t_open_wall": t_open_wall,
        "window_s": t_close - t_open,
        "window_steps": len(window),
        "compiled_first_step": ts._step._cache_size() != cache_before,
        "compiles_in_window": cache_at_close - cache_at_open,
        "nonfinite_in_window": sum(not math.isfinite(r["loss"]) for r in window),
        "reference": ref, "system": got, "rel_diff": rel,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "trace_file": trace_file,
    })

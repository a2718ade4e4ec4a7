"""The controls of the comparison for bench/families/granite.py, on the chip,
by hand and outside any cell:

    chiprun -- python3 bench/tests/granite_control.py [--seeds 3] [--first-seed N]

At the size of the cell granite4_h_micro_l10.t4096 (its configuration, its
traffic mix, weights and first batch from the seed as bench/worker.py makes
them) the plain float32 reference is computed once as it is and once with a
known fault in it, and the faulty one is put in the system's place: `rel_diff`
of its loss and of its global gradient norm against the sound reference's,
beside `bench/run.py:TOLERANCE`. The faults:

    bf16     every matmul's operands rounded to bfloat16 by
             `jax.lax.reduce_precision`: what the sound system does; passes
    fp8      the same to fp8 e4m3, scaled per tensor (never a cast: the TPU's
             compiler takes a cast out again, PERF.md, PR 28): the nearest
             precision below the configuration's
    carry    the carried state dropped at every 256th step: a chunked scan
             that lost what it hands from chunk to chunk
    decay    the cumulative log-decay inside each chunk of 256 rounded to
             bfloat16: a kernel that keeps its decays in the compute dtype

Each line says whether the fault would pass. With --scan the same faults one
level down, where a scalar loss does not average them away: the scan's output
y of the first Mamba layer (the reference's own activations as inputs) by the
reference's recurrence with each fault, and by the system's own ops/ssd.py in
the configuration's compute dtype (the kernels, on a TPU), each against the
sound recurrence as |y' - y| / |y| (root of the summed squares) and as the
largest error over the largest value. --cpu is a rehearsal at the rehearsal
sizes: it proves the path and gives no number."""

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 3600)
    ap.add_argument("--scan", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from bench import families, traffic
    from bench.run import TOLERANCE
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    if not args.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: this control is a chip reading (--cpu rehearses)")
    with open(os.path.join(ROOT, "bench", "configs", "granite4_h_micro_l10.json")) as f:
        sizes = json.load(f)
    mix = traffic.load("b1_t4096", rehearse=args.cpu)
    if args.cpu:
        sizes.update(sizes["rehearsal"])
    fam = families.load(sizes["family"])
    chunk = sizes["mamba_chunk_size"]

    def straight_through(rounding):
        # the value rounded, the gradient as if it were not: reduce_precision's
        # own rule rounds the cotangent too, and in e4m3 flushes most of it
        return lambda x: x + jax.lax.stop_gradient(rounding(x) - x)

    @straight_through
    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    @straight_through
    def fp8(x):
        scale = 240.0 / jnp.max(jnp.abs(x))
        return jax.lax.reduce_precision(x * scale, exponent_bits=4, mantissa_bits=3) / scale

    faults = {"sound": {}, "bf16": {"OPERAND": bf16}, "fp8": {"OPERAND": fp8},
              "carry": {"RESET_EVERY": chunk}, "decay": {"LOG_DECAY": (bf16, chunk)}}

    def program():
        """Loss and global gradient norm of the whole reference, traced at
        its first call with whatever fault the family's hooks then hold."""
        def run(params, idx, targets):
            loss, grads = jax.value_and_grad(
                lambda p: families.reference_loss(fam, p, idx, targets, sizes))(params)
            return loss, jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        return jax.jit(run)

    def set_hooks(hooks):
        for hook in ("OPERAND", "RESET_EVERY", "LOG_DECAY"):
            setattr(fam, hook, hooks.get(hook))

    def scan(params, idx):
        """y of the first Mamba layer's scan, sound and with each fault, and
        by the system's ops/ssd.py, from the reference's own activations."""
        from ray_tpu.ops.ssd import ssd

        mixer = params[fam.layer_names(sizes)[0]]["h_0"]
        set_hooks({})
        with jax.default_matmul_precision("highest"):
            u = fam._rms_norm(fam.embed({"tok_emb": params["tok_emb"]}, idx, sizes),
                              mixer["mixer_norm"]["weight"], sizes["rms_norm_eps"])
            _, x, delta, a, bm, cm = fam._scan_inputs(u, mixer["mamba"], sizes)
            ys = {}
            for name in ("sound", "carry", "decay"):
                set_hooks(faults[name])
                # a function of its own: the hooks are read where it is traced
                ys[name] = jax.jit(lambda *args: fam._recurrence(*args))(x, delta, a, bm, cm)
            set_hooks({})
        dtype = jnp.dtype(sizes["compute_dtype"])
        ys["system"] = ssd(x.astype(dtype), delta, a, bm.astype(dtype), cm.astype(dtype),
                           jnp.zeros_like(a), chunk)[0].astype(jnp.float32)
        sound = ys.pop("sound")
        return {name: {"l2": float(jnp.linalg.norm(y - sound) / jnp.linalg.norm(sound)),
                       "max": float(jnp.abs(y - sound).max() / jnp.abs(sound).max())}
                for name, y in ys.items()}

    programs = {name: program() for name in faults}
    ts = TrainStep(fam.build(sizes, sizes["compute_dtype"]),
                   make_mesh(sizes["mesh"], devices=jax.devices()[:1]), telemetry=False)
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = ts.init(jax.random.PRNGKey(seed & 0xFFFFFFFF))["params"]
        batch = traffic.make_batch(mix, sizes["vocab_size"], seed, 0)
        idx, targets = jnp.asarray(batch["idx"]), jnp.asarray(batch["targets"])
        if args.scan:
            print(json.dumps({"seed": seed, "scan_output_against_the_sound_recurrence":
                              scan(params, idx)}), flush=True)
            continue
        read = {}
        for name, hooks in faults.items():
            set_hooks(hooks)
            loss, gnorm = programs[name](params, idx, targets)
            read[name] = {"loss": float(loss), "grad_norm": float(gnorm)}
        set_hooks({})
        for name in list(faults)[1:]:
            rel = {k: abs(read[name][k] - read["sound"][k]) / abs(read["sound"][k])
                   for k in TOLERANCE}
            finite = all(math.isfinite(v) for v in rel.values())
            print(json.dumps({
                "seed": seed, "fault": name, "rel_diff": rel, "tolerance": TOLERANCE,
                **read[name], "sound": read["sound"],
                "would_pass": bool(finite and all(rel[k] <= TOLERANCE[k] for k in TOLERANCE))}),
                flush=True)
        del params


if __name__ == "__main__":
    main()

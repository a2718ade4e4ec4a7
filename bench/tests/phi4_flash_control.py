"""The controls of the comparison for bench/families/phi4_flash.py, on the
chip, by hand and outside any cell:

    chiprun -- python3 bench/tests/phi4_flash_control.py [--seeds 1] [--first-seed N]

At the size of the cell phi4_mini_flash_l5.t16384 (its configuration, its
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
    carry    the carried state dropped at every 128th step: a scan that lost
             what it hands from chunk to chunk (ops/selective_scan.py's chunk)
    memory   the memory taken after the Mamba layer's gate, y * silu(z), and
             not before it
    lambda   lambda's second term dropped: exp(lq1 . lk1) + lambda_init
    norm     the RMSNorm over a pair's 128 values after the difference dropped
    decay    a step's decay exp(Delta A) rounded to bfloat16: a kernel that
             keeps its decays in the compute dtype

Each line says whether the fault would pass. What passes here is held
elsewhere (PERF.md section 7): the kernels against the recurrence by
chip_smoke.py's `sscan_vs_recurrence` and tests/test_selective_scan.py.
--cpu is a rehearsal at the rehearsal sizes: it proves the path and gives no
number."""

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# the family's hooks and what each holds in every other use
HOOKS = {"OPERAND": None, "RESET_EVERY": None, "DECAY": None, "MEMORY_AFTER_GATE": False,
         "NO_SECOND_LAMBDA": False, "NO_DIFF_NORM": False}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 5700)
    ap.add_argument("--fault", action="append", help="only these (default: all)")
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
    with open(os.path.join(ROOT, "bench", "configs", "phi4_mini_flash_l5.json")) as f:
        sizes = json.load(f)
    mix = traffic.load("b1_t16384", rehearse=args.cpu)
    if args.cpu:
        sizes.update(sizes["rehearsal"])
    fam = families.load(sizes["family"])

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
              "carry": {"RESET_EVERY": 128}, "memory": {"MEMORY_AFTER_GATE": True},
              "lambda": {"NO_SECOND_LAMBDA": True}, "norm": {"NO_DIFF_NORM": True},
              "decay": {"DECAY": bf16}}
    if args.fault:
        faults = {name: faults[name] for name in ["sound"] + args.fault}

    def program():
        """Loss and global gradient norm of the whole reference, traced at
        its first call with whatever fault the family's hooks then hold."""
        def run(params, idx, targets):
            loss, grads = jax.value_and_grad(
                lambda p: families.reference_loss(fam, p, idx, targets, sizes))(params)
            return loss, jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        return jax.jit(run)

    def set_hooks(hooks):
        for hook, unset in HOOKS.items():
            setattr(fam, hook, hooks.get(hook, unset))

    programs = {name: program() for name in faults}
    ts = TrainStep(fam.build(sizes, sizes["compute_dtype"]),
                   make_mesh(sizes["mesh"], devices=jax.devices()[:1]), telemetry=False)
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = ts.init(jax.random.PRNGKey(seed & 0xFFFFFFFF))["params"]
        batch = traffic.make_batch(mix, sizes["vocab_size"], seed, 0)
        idx, targets = jnp.asarray(batch["idx"]), jnp.asarray(batch["targets"])
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

"""The controls of the comparison for the routed family of
bench/families/nemotron_h.py, on the chip, by hand and outside any cell
(bench/tests/kanana_control.py is the pattern, bench/tests/granite_control.py
the faults' source):

    chiprun -- python3 bench/tests/nemotron_h_control.py [--fault fp8] [--seeds 8] [--first-seed N]

At the size of the cell nemotron3_nano_l9_ep16.t8192 (its configuration, its
traffic mix, weights and first batch from the seed as bench/worker.py makes
them), the plain reference with a known fault in it is put in the system's
place. It runs forward by its own choices (the group's four expert blocks'
stacked); the sound float32 reference then takes those choices as it takes
the system's in bench/worker.py, and the two numbers the harness judges of a
forward pass are printed beside their limits: `choice_agreement` and the held
`rel_diff` of the loss. The faults:

    fp8      every matmul's operands (the attention's q, k and v and the
             scan's x, B and C among them) rounded to fp8 e4m3 by
             `jax.lax.reduce_precision`, scaled per tensor, never by a cast
             (the TPU's compiler takes a cast out again: PERF.md, PR 28): the
             nearest precision below the configuration's bfloat16
    carry    the carried state dropped at every 128th step: a chunked scan
             that lost what it hands from chunk to chunk
    group0   B and C of group 0 given to every head: kernels that take one
             group, handed eight
    decay    the cumulative log-decay inside each chunk of 128 rounded to
             bfloat16: a kernel that keeps its decays in the compute dtype
    bf16     the operands rounded to bfloat16, which is what the sound system
             does and has to pass

Each of the first three has to come out as not correct. --cpu is a rehearsal
at the rehearsal sizes: it proves the path and gives no number."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

HOOKS = ("OPERAND", "RESET_EVERY", "LOG_DECAY", "ONE_GROUP")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", default="fp8", choices=("fp8", "carry", "group0", "decay", "bf16"))
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 9800)
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
    with open(os.path.join(ROOT, "bench", "configs", "nemotron3_nano_l9_ep16.json")) as f:
        sizes = json.load(f)
    mix = traffic.load("b2_t8192", rehearse=args.cpu)
    if args.cpu:
        sizes.update(sizes["rehearsal"])
    fam = families.load(sizes["family"])
    names = fam.layer_names(sizes)
    chunk = sizes["chunk_size"]

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def fp8(x):
        scale = 240.0 / jnp.max(jnp.abs(x))
        return jax.lax.reduce_precision(x * scale, exponent_bits=4, mantissa_bits=3) / scale

    fault = {"fp8": {"OPERAND": fp8}, "bf16": {"OPERAND": bf16}, "carry": {"RESET_EVERY": chunk},
             "group0": {"ONE_GROUP": True}, "decay": {"LOG_DECAY": (bf16, chunk)}}[args.fault]

    def set_hooks(hooks):
        for hook in HOOKS:
            setattr(fam, hook, hooks.get(hook, False if hook == "ONE_GROUP" else None))

    def programs():
        """embed, choice, layer and head as jitted programs, traced with
        whatever the family's hooks hold when they are first called."""
        return (jax.jit(lambda o, idx: fam.embed(o, idx, sizes)),
                jax.jit(lambda x, blk: fam.choice(x, blk, sizes)),
                jax.jit(lambda x, blk, c: fam.layer(x, blk, sizes, choice=c)),
                jax.jit(lambda o, x, t: fam.head_loss(o, x, t, sizes)))

    # two sets of programs: each is traced at its first call, under the hooks
    # the loop below sets before it calls them
    sound, faulty = programs(), programs()

    @jax.jit
    def agreement(theirs, own):
        return (theirs[..., :, None] == own[..., None, :]).any(-1).mean()

    ts = TrainStep(fam.build(sizes, sizes["compute_dtype"]),
                   make_mesh(sizes["mesh"], devices=jax.devices()[:1]), telemetry=False)
    rows = mix["reference_rows"]
    parts = mix["batch"] // rows
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = ts.init(jax.random.PRNGKey(seed & 0xFFFFFFFF))["params"]
        _, outer = families.split_params(fam, params, sizes)
        batch = traffic.make_batch(mix, sizes["vocab_size"], seed, 0)
        loss_faulty = loss_held = 0.0
        agree = []
        for i in range(parts):
            idx, tgt = (jnp.asarray(batch[k][i * rows:(i + 1) * rows]) for k in ("idx", "targets"))
            set_hooks(fault)
            embed, choice, layer, head = faulty
            x, chosen = embed(outer, idx), {}
            for name in names:
                chosen[name] = choice(x, params[name])
                x = layer(x, params[name], chosen[name])
            loss_faulty += float(head(outer, x, tgt)) / parts
            set_hooks({})
            embed, choice, layer, head = sound
            x = embed(outer, idx)
            for name in names:
                agree.append(float(agreement(chosen[name], choice(x, params[name]))))
                x = layer(x, params[name], chosen[name])
            loss_held += float(head(outer, x, tgt)) / parts
        share = sum(agree) / len(agree)
        rel = abs(loss_faulty - loss_held) / abs(loss_held)
        passes = share >= sizes["choice_agreement_min"] and rel <= TOLERANCE["loss"]
        print(json.dumps({
            "seed": seed, "fault": args.fault,
            "choice_agreement": share, "choice_agreement_min": sizes["choice_agreement_min"],
            "rel_diff_loss_held": rel, "tolerance_loss": TOLERANCE["loss"],
            "loss": loss_faulty, "loss_reference_held": loss_held, "would_pass": bool(passes)}),
            flush=True)
        del params, outer


if __name__ == "__main__":
    main()

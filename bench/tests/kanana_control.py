"""The control of the comparison for the routed family of
bench/families/kanana.py, on the chip, by hand and outside any cell
(bench/tests/lfm2_control.py is the pattern, and this is it with the
configuration and the traffic as options):

    chiprun -- python3 bench/tests/kanana_control.py [--seeds 8] [--first-seed N]

At the size of the cell kanana2_30b_l5_ep8.t8192 (its configuration, its
traffic mix, weights and first batch from the seed as bench/worker.py makes
them), the plain reference is put in the system's place with the operands of
every matmul (the attention's two among them, q, k, v and the probabilities)
rounded to the nearest precision below the configuration's bfloat16: fp8 (e4m3) by `jax.lax.reduce_precision`, scaled per tensor, never
by a cast (the TPU's compiler takes a cast out again: PERF.md, PR 28). It
runs forward by its own choices (the group's four routed blocks' stacked); the float32 reference then takes those
choices as it takes the system's in bench/worker.py, and the two numbers the
harness judges are printed beside their limits: `choice_agreement` and the
held `rel_diff` of the loss. The control has to come out as not correct.
With --bf16 the same with operands rounded to bfloat16, which is what the
sound system does and has to pass.

--cpu is a rehearsal at the rehearsal sizes: it proves the path and gives no
number."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 9700)
    ap.add_argument("--config", default="kanana2_30b_l5_ep8")
    ap.add_argument("--traffic", default="b2_t8192")
    ap.add_argument("--bf16", action="store_true")
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
    with open(os.path.join(ROOT, "bench", "configs", f"{args.config}.json")) as f:
        sizes = json.load(f)
    mix = traffic.load(args.traffic, rehearse=args.cpu)
    if args.cpu:
        sizes.update(sizes["rehearsal"])
    fam = families.load(sizes["family"])
    names = fam.layer_names(sizes)

    def rounded(x):
        if args.bf16:
            return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        scale = 240.0 / jnp.max(jnp.abs(x))
        return jax.lax.reduce_precision(x * scale, exponent_bits=4, mantissa_bits=3) / scale

    def programs():
        """embed, choice, layer and head as jitted programs, traced with
        whatever `fam.OPERAND` holds when this is called."""
        return (jax.jit(lambda o, idx: fam.embed(o, idx, sizes)),
                jax.jit(lambda x, blk: fam.choice(x, blk, sizes)),
                jax.jit(lambda x, blk, c: fam.layer(x, blk, sizes, choice=c)),
                jax.jit(lambda o, x, t: fam.head_loss(o, x, t, sizes)))

    # two sets of programs: each is traced at its first call, under the
    # `fam.OPERAND` the loop below sets before it calls them
    plain, low = programs(), programs()

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
        loss_low = loss_held = 0.0
        agree = []
        for i in range(parts):
            idx, tgt = (jnp.asarray(batch[k][i * rows:(i + 1) * rows]) for k in ("idx", "targets"))
            fam.OPERAND = rounded
            embed, choice, layer, head = low
            x, chosen = embed(outer, idx), {}
            for name in names:
                chosen[name] = choice(x, params[name])
                x = layer(x, params[name], chosen[name])
            loss_low += float(head(outer, x, tgt)) / parts
            fam.OPERAND = None
            embed, choice, layer, head = plain
            x = embed(outer, idx)
            for name in names:
                agree.append(float(agreement(chosen[name], choice(x, params[name]))))
                x = layer(x, params[name], chosen[name])
            loss_held += float(head(outer, x, tgt)) / parts
        share = sum(agree) / len(agree)
        rel = abs(loss_low - loss_held) / abs(loss_held)
        passes = share >= sizes["choice_agreement_min"] and rel <= TOLERANCE["loss"]
        print(json.dumps({
            "seed": seed, "operands": "bf16" if args.bf16 else "fp8_e4m3",
            "choice_agreement": share, "choice_agreement_min": sizes["choice_agreement_min"],
            "rel_diff_loss_held": rel, "tolerance_loss": TOLERANCE["loss"],
            "loss": loss_low, "loss_reference_held": loss_held, "would_pass": bool(passes)}),
            flush=True)
        del params, outer


if __name__ == "__main__":
    main()

"""The control of the comparison for the family of bench/families/sdar.py, on
the chip, by hand and outside any cell:

    chiprun -- python3 bench/tests/sdar_control.py [--seeds 3] [--first-seed N]
                                                   [--faults fp8,...] [--mask]

At the size of the cell sdar_30b_a3b_l5_ep8.t8192 (its configuration, its
traffic mix, weights and first batch from the seed as bench/worker.py makes
them), the plain reference is put in the system's place with a fault in it,
runs forward by its own choices of experts, and the sound reference then takes
those choices as it takes the system's in bench/worker.py: the two numbers the
harness judges are printed beside their limits, `choice_agreement` and the held
`rel_diff` of the loss, and whether the fault would pass. The faults:

    fp8      the operands of every matmul rounded to the nearest precision
             below the configuration's bfloat16, fp8 (e4m3) by
             `jax.lax.reduce_precision`, scaled per tensor, never a cast
             (bench/tests/keye_control.py's pattern): has to come out as not
             correct
    bf16     the same at bfloat16, which is what the sound system does: has
             to pass
    clean_copy     a wrong mask: a noised block also reads its own *clean*
             copy (noised q, clean k: blk(j) <= blk(i)), which hands every
             masked token its answer
    causal_within  a wrong mask: a noised block is causal within itself
             (noised q, noised k: blk(j) == blk(i) and j <= i)
    dead_past      a gross wrong mask: the noised-to-clean quadrant is dead (a
             noised block reads itself alone, no clean past): has to come
             out as not correct
    causal_2t      a gross wrong mask: a causal call over the 2T positions of
             the stream as it lies, [noised | clean] (a noised query reads
             the noised tokens up to itself, a clean query every noised token
             and the clean ones up to itself): has to come out as not correct

With random weights a softmax over thousands of keys barely moves for four, so
the scalar comparison may pass a wrong mask on the block's edges: the line says
what it read, and the two gross ones show that it refuses a whole quadrant
gone wrong. What holds the mask's edges is one level down: with --mask the attention's own contribution
to the stream in the first layer (what it adds to the noised half), by each
wrong mask and by the system's own layer (ops/attention.py's pair on a TPU)
against the sound reference's on the same input, and chip_smoke.py's
`flash_bd_vs_xla`, the pair against the XLA form at the cell's tiles.

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
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 6100)
    ap.add_argument("--faults", default="fp8,bf16,clean_copy,causal_within,dead_past,causal_2t")
    ap.add_argument("--mask", action="store_true")
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
    with open(os.path.join(ROOT, "bench", "configs", "sdar_30b_a3b_l5_ep8.json")) as f:
        sizes = json.load(f)
    mix = traffic.load("b1_t8192", rehearse=args.cpu)
    if args.cpu:
        sizes.update(sizes["rehearsal"])
    fam = families.load(sizes["family"])
    names = fam.layer_names(sizes)

    def fp8(x):
        scale = 240.0 / jnp.max(jnp.abs(x))
        return jax.lax.reduce_precision(x * scale, exponent_bits=4, mantissa_bits=3) / scale

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def clean_copy(q_clean, i, k_clean, j, L):
        return fam.seen(q_clean, i, k_clean, j, L) | (~q_clean & k_clean & (j // L == i // L))

    def causal_within(q_clean, i, k_clean, j, L):
        return fam.seen(q_clean, i, k_clean, j, L) & (q_clean | k_clean | (j <= i))

    def dead_past(q_clean, i, k_clean, j, L):
        return fam.seen(q_clean, i, k_clean, j, L) & (q_clean | ~k_clean)

    def causal_2t(q_clean, i, k_clean, j, L):
        return (q_clean & ~k_clean) | ((q_clean == k_clean) & (j <= i))

    faults = {"sound": {}, "fp8": {"OPERAND": fp8}, "bf16": {"OPERAND": bf16},
              "clean_copy": {"SEEN": clean_copy}, "causal_within": {"SEEN": causal_within},
              "dead_past": {"SEEN": dead_past}, "causal_2t": {"SEEN": causal_2t}}

    def set_hooks(hooks):
        fam.OPERAND, fam.SEEN = hooks.get("OPERAND"), hooks.get("SEEN", fam.seen)

    def programs():
        """embed, choice, layer and head as jitted programs, traced at their
        first call with whatever the family's hooks then hold."""
        return (jax.jit(lambda o, idx: fam.embed(o, idx, sizes)),
                jax.jit(lambda x, blk: fam.choice(x, blk, sizes)),
                jax.jit(lambda x, blk, c: fam.layer(x, blk, sizes, choice=c)[0]),
                jax.jit(lambda o, x, t: fam.head_loss(o, x, t, sizes)))

    wanted = [f for f in args.faults.split(",") if f]
    made = {name: programs() for name in ["sound"] + wanted}

    @jax.jit
    def agreement(theirs, own):
        return (theirs[..., :, None] == own[..., None, :]).any(-1).mean()

    cfg = fam.build(sizes, sizes["compute_dtype"])
    ts = TrainStep(cfg, make_mesh(sizes["mesh"], devices=jax.devices()[:1]), telemetry=False)

    def attention_added(params, x):
        """What the first layer's attention adds to the noised half of the
        stream x: by each wrong mask, and by the system's own layer, against
        the sound reference's."""
        from ray_tpu.models.layers import LlamaAttention, RMSNorm

        blk, half = params[names[0]], x.shape[1] // 2
        added = {}
        for name in ("sound", "clean_copy", "causal_within"):
            set_hooks(faults[name])
            # a function of its own: the hooks are read where it is traced
            added[name] = (jax.jit(lambda x, blk: fam.attend(x, blk, sizes))(x, blk) - x)[:, :half]
        set_hooks({})

        @jax.jit
        def system(x, blk):
            h = RMSNorm(cfg.rms_eps).apply({"params": blk["attn_norm"]}, x.astype(cfg.dtype))
            return LlamaAttention(cfg, qk_norm=True, blocks=cfg.block_length).apply(
                {"params": blk["attn"]}, h).astype(jnp.float32)

        added["system"] = system(x, blk)[:, :half]
        sound = added.pop("sound")
        return {name: {"l2": float(jnp.linalg.norm(y - sound) / jnp.linalg.norm(sound)),
                       "max": float(jnp.abs(y - sound).max() / jnp.abs(sound).max())}
                for name, y in added.items()}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = ts.init(jax.random.PRNGKey(seed & 0xFFFFFFFF))["params"]
        _, outer = families.split_params(fam, params, sizes)
        batch = traffic.make_batch(mix, sizes["vocab_size"], seed, 0)
        idx, tgt = (jnp.asarray(batch[k]) for k in ("idx", "targets"))
        if args.mask:
            set_hooks({})
            print(json.dumps({"seed": seed, "attention_added_to_the_noised_half_against_the_sound":
                              attention_added(params, made["sound"][0](outer, idx))}), flush=True)
        for fault in wanted:
            set_hooks(faults[fault])
            embed, choice, layer, head = made[fault]
            x, chosen = embed(outer, idx), {}
            for name in names:
                chosen[name] = choice(x, params[name])
                x = layer(x, params[name], chosen[name])
            loss_fault = float(head(outer, x, tgt))
            set_hooks({})
            embed, choice, layer, head = made["sound"]
            x, agree = embed(outer, idx), []
            for name in names:
                agree.append(float(agreement(chosen[name], choice(x, params[name]))))
                x = layer(x, params[name], chosen[name])
            loss_held = float(head(outer, x, tgt))
            share = sum(agree) / len(agree)
            rel = abs(loss_fault - loss_held) / abs(loss_held)
            print(json.dumps({
                "seed": seed, "fault": fault,
                "choice_agreement": share, "choice_agreement_min": sizes["choice_agreement_min"],
                "choice_agreement_by_layer": agree,
                "rel_diff_loss_held": rel, "tolerance_loss": TOLERANCE["loss"],
                "loss": loss_fault, "loss_reference_held": loss_held,
                "would_pass": bool(share >= sizes["choice_agreement_min"]
                                   and rel <= TOLERANCE["loss"])}), flush=True)
        del params, outer


if __name__ == "__main__":
    main()

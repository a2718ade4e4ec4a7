"""The controls of the comparison for the routed family of
bench/families/qwen3_next.py, on the chip, by hand and outside any cell
(bench/tests/kimi_linear_control.py is the pattern):

    chiprun -- python3 bench/tests/qwen3_next_control.py [--fault fp8,carry] [--seeds 4] [--first-seed N]
    chiprun -- python3 bench/tests/qwen3_next_control.py --rows [--seeds 4] [--steps 40]

At the size of the cell qwen3_next_80b_l5_ep32.t8192 (its configuration, its
traffic mix, weights and first batch from the seed as bench/worker.py makes
them), the plain reference with a known fault in it is put in the system's
place. It runs forward by its own choices (the group's five expert blocks'
stacked); the sound float32 reference then takes those choices as it takes
the system's in bench/worker.py, and the two numbers the harness judges of a
forward pass are printed beside their limits: `choice_agreement` and the held
`rel_diff` of the loss. The faults (several, separated by commas, run one
after another on the same seeds):

    fp8          every matmul's operands (the attention's q, k and v and the
                 delta rule's q, k and v among them) rounded to fp8 e4m3 by
                 `jax.lax.reduce_precision`, scaled per tensor, never by a
                 cast: the nearest precision below the configuration's
                 bfloat16
    carry        the carried state dropped at every 64th step: a chunked
                 delta rule that lost what it hands from chunk to chunk
    delta        beta k k^T S left out of the transition
    decay_after  the decay applied after the update and not before it
    key_head     value head j reads key head j (mod 16) in place of j // 2
    rotary_all   the rotary over all 256 of a head, not its first 64
    bf16         the operands rounded to bfloat16, which is what the sound
                 system does and has to pass

Each but the last has to come out as not correct; where a scalar loss lets
one pass, PERF.md section 7 names what does hold it (the choices' floor, the
kernel tests, chip_smoke.py's `gdn_vs_plain`). --rows reads what
`ExpertShare.headroom` rests on instead: over `--seeds` seeds and `--steps`
steps of the system's own training, the rows routed to the 16 held experts
of each layer over the even load. --cpu is a rehearsal at the rehearsal
sizes: it proves the path and gives no number."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

HOOKS = {"OPERAND": None, "RESET_EVERY": None, "NO_DELTA_TERM": False, "DECAY_AFTER": False,
         "KEY_HEAD_J": False, "ROTARY_ALL": False}
FAULTS = ("fp8", "carry", "delta", "decay_after", "key_head", "rotary_all", "bf16")


def held_rows(args, ts, sizes, mix):
    """A line a seed: over `--steps` steps of training, each layer's rows
    routed to the held experts over the even load, the largest and the mean."""
    import jax

    from bench import traffic

    even = mix["batch"] * mix["seq_len"] * sizes["num_experts_per_tok"] * sizes["num_experts"] \
        / sizes["num_experts_published"]
    loads = jax.jit(lambda p, idx: ts.model.apply({"params": p}, idx, mutable=["moe_load"])[1])
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        state = ts.init(jax.random.PRNGKey(seed & 0xFFFFFFFF))
        ratios, summed = [], []
        for step in range(args.steps):
            batch = ts.shard_batch(traffic.make_batch(mix, sizes["vocab_size"], seed, step))
            sown = jax.tree_util.tree_flatten_with_path(loads(state["params"], batch["idx"]))[0]
            ratios.append([float(v.sum()) / even for path, v in sown
                           if "rows" in jax.tree_util.keystr(path)])
            state, m = ts.step(state, batch)
            summed.append(float(m["moe_rows_summed_share"]))
        by_layer = list(zip(*ratios))
        print(json.dumps({
            "seed": seed, "steps": args.steps, "even_rows_a_layer": even,
            "rows_over_even_max": max(map(max, by_layer)),
            "rows_over_even_max_by_layer": [max(layer) for layer in by_layer],
            "rows_over_even_mean_by_layer": [sum(layer) / len(layer) for layer in by_layer],
            "moe_rows_summed_share_max": max(summed)}), flush=True)
        del state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", default="fp8")
    ap.add_argument("--rows", action="store_true")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 11400)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    faults = args.fault.split(",")
    if [f for f in faults if f not in FAULTS]:
        raise SystemExit(f"--fault takes {FAULTS}, separated by commas")
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
    with open(os.path.join(ROOT, "bench", "configs", "qwen3_next_80b_l5_ep32.json")) as f:
        sizes = json.load(f)
    mix = traffic.load("b2_t8192", rehearse=args.cpu)
    if args.cpu:
        sizes.update(sizes["rehearsal"])
    fam = families.load(sizes["family"])
    names = fam.layer_names(sizes)
    ts = TrainStep(fam.build(sizes, sizes["compute_dtype"]),
                   make_mesh(sizes["mesh"], devices=jax.devices()[:1]), telemetry=False)
    if args.rows:
        return held_rows(args, ts, sizes, mix)

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def fp8(x):
        scale = 240.0 / jnp.max(jnp.abs(x))
        return jax.lax.reduce_precision(x * scale, exponent_bits=4, mantissa_bits=3) / scale

    hooks_of = {"fp8": {"OPERAND": fp8}, "bf16": {"OPERAND": bf16}, "carry": {"RESET_EVERY": 64},
                "delta": {"NO_DELTA_TERM": True}, "decay_after": {"DECAY_AFTER": True},
                "key_head": {"KEY_HEAD_J": True}, "rotary_all": {"ROTARY_ALL": True}}

    def set_hooks(hooks):
        for hook, sound in HOOKS.items():
            setattr(fam, hook, hooks.get(hook, sound))

    def programs():
        """embed, choice, layer and head as jitted programs, traced with
        whatever the family's hooks hold when they are first called."""
        return (jax.jit(lambda o, idx: fam.embed(o, idx, sizes)),
                jax.jit(lambda x, blk: fam.choice(x, blk, sizes)),
                jax.jit(lambda x, blk, c: fam.layer(x, blk, sizes, choice=c)),
                jax.jit(lambda o, x, t: fam.head_loss(o, x, t, sizes)))

    # a set of programs the sound reference and one a fault: each is traced at
    # its first call, under the hooks the loop below sets before it calls them
    sound, faulty = programs(), {fault: programs() for fault in faults}

    @jax.jit
    def agreement(theirs, own):
        return (theirs[..., :, None] == own[..., None, :]).any(-1).mean()

    rows = mix["reference_rows"]
    parts = mix["batch"] // rows
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = ts.init(jax.random.PRNGKey(seed & 0xFFFFFFFF))["params"]
        _, outer = families.split_params(fam, params, sizes)
        batch = traffic.make_batch(mix, sizes["vocab_size"], seed, 0)
        for fault in faults:
            loss_faulty = loss_held = 0.0
            agree = []
            for i in range(parts):
                idx, tgt = (jnp.asarray(batch[k][i * rows:(i + 1) * rows])
                            for k in ("idx", "targets"))
                set_hooks(hooks_of[fault])
                embed, choice, layer, head = faulty[fault]
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
                "seed": seed, "fault": fault,
                "choice_agreement": share, "choice_agreement_min": sizes["choice_agreement_min"],
                "rel_diff_loss_held": rel, "tolerance_loss": TOLERANCE["loss"],
                "loss": loss_faulty, "loss_reference_held": loss_held,
                "would_pass": bool(passes)}), flush=True)
        del params, outer


if __name__ == "__main__":
    main()

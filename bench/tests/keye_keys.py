"""How far the system's own selection of keys agrees with the reference's,
on the chip, by hand and outside any cell:

    chiprun -- python3 bench/tests/keye_keys.py [--seeds 2] [--first-seed N]

The cell keye_vl2_30b_l4_ep8.t16384 holds the experts' choice and lets each
side select its own keys (bench/families/keye.py). This prints, at the cell's
size and for each layer, the share of a query's keys that the system's
indexer (models/mellum.py:Indexer, bfloat16, the pallas calls of
ops/indexer.py) and the float32 reference both selected, on the same input:
the reference's own activations, by the system's experts. Judged by nothing.

--cpu is a rehearsal at the rehearsal sizes."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 3500)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from bench import families, traffic
    from ray_tpu.models.llama import RMSNorm
    from ray_tpu.models.mellum import Indexer
    from ray_tpu.ops import indexer
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.parallel.train_step import TrainStep

    if not args.cpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU: this is a chip reading (--cpu rehearses)")
    with open(os.path.join(ROOT, "bench", "configs", "keye_vl2_30b_l4_ep8.json")) as f:
        sizes = json.load(f)
    mix = traffic.load("b1_t16384", rehearse=args.cpu)
    if args.cpu:
        sizes.update(sizes["rehearsal"])
    fam = families.load(sizes["family"])
    names = fam.layer_names(sizes)
    cfg = fam.build(sizes, sizes["compute_dtype"])
    ts = TrainStep(cfg, make_mesh(sizes["mesh"], devices=jax.devices()[:1]), telemetry=False)

    @jax.jit
    def system_keys(x, blk):
        h = RMSNorm(cfg.rms_eps).apply({"params": blk["attn_norm"]}, x.astype(cfg.dtype))
        return indexer.unpack(Indexer(cfg).apply({"params": blk["indexer"]}, h)[0])

    reference_keys = jax.jit(lambda x, blk: fam.selected_keys(x, blk, sizes))
    layer = jax.jit(lambda x, blk, c: fam.layer(x, blk, sizes, choice=c)[0])
    experts = jax.jit(lambda p, idx: ts.model.apply(
        {"params": p}, idx, mutable=["choices"])[1]["choices"])

    @jax.jit
    def both(theirs, own):
        return (theirs & own).sum() / own.sum(), (theirs & own).sum(-1).min(), own.sum(-1).mean()

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = ts.init(jax.random.PRNGKey(seed & 0xFFFFFFFF))["params"]
        _, outer = families.split_params(fam, params, sizes)
        batch = traffic.make_batch(mix, sizes["vocab_size"], seed, 0)
        idx = jnp.asarray(batch["idx"][:mix["reference_rows"]])
        held = experts(params, idx)
        x, shares, least = fam.embed(outer, idx, sizes), [], []
        for name in names:
            share, fewest, mean_keys = both(system_keys(x, params[name]),
                                            reference_keys(x, params[name]))
            shares.append(float(share))
            least.append(int(fewest))
            x = layer(x, params[name], jax.tree.leaves(held[name])[0].reshape(
                idx.shape + (sizes["num_experts_per_tok"],)))
        print(json.dumps({"seed": seed, "key_agreement_by_layer": shares,
                          "key_agreement": sum(shares) / len(shares),
                          "fewest_common_keys_of_a_query": least,
                          "keys_a_query_mean": float(mean_keys)}), flush=True)
        del params, outer


if __name__ == "__main__":
    main()

"""The one general generator of training traffic. A traffic mix is a data
file of parameters under bench/traffic/; this reads it. The program never
sees the seed, only the batches."""

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name, rehearse=False):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    small = mix.pop("rehearsal", {})
    if rehearse:
        mix.update(small)
    return mix


def _uniform_packed(mix, vocab_size, rng):
    """Packed sequences of uniform random token ids, targets shifted by one:
    every seed and step gives the same shapes, so no seed changes the work."""
    tokens = rng.integers(
        0, vocab_size, (mix["batch"], mix["seq_len"] + 1), dtype=np.int32)
    return {"idx": tokens[:, :-1], "targets": tokens[:, 1:]}


GENERATORS = {"uniform_packed": _uniform_packed}


def make_batch(mix, vocab_size, seed, step):
    """The batch of one step, from the seed and the step number alone."""
    rng = np.random.default_rng([int(seed), int(step)])
    return GENERATORS[mix["generator"]](mix, vocab_size, rng)


def tokens_per_step(mix):
    return mix["batch"] * mix["seq_len"]

"""The comparison that decides `correct`, shown to fail: the control — the
reference itself in the nearest precision below the stated one — reads a
wider gap than the limit, at a size a test run can hold; the reference's own
greedy tokens read 0. Also: the served tree and the reference's weights are
the same numbers, though one is made in one call and the other layer by
layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import spec
import weights

EMIT = list(range(2, 200))
# Both configurations are of one family; its parts, found as a run finds them.
LLAMA = spec.load_json("configs", "mistral-7b-int8.json")
program = spec.family(LLAMA, "program")
fam_weights = spec.family(LLAMA, "weights")
fam_reference = spec.family(LLAMA, "reference")


def _tiny(name):
    cfg = spec.load_json("configs", name + ".json")
    return {**cfg, **cfg["rehearsal"]}


@pytest.mark.parametrize("name", ["mistral-7b-int8", "smollm2-1.7b-bf16"])
def test_served_tree_is_the_reference_weights(name):
    cfg = _tiny(name)
    fmt = cfg["serving"]["weights"]
    tree = program.served_tree(cfg, fmt, 2**31 + 5, EMIT)
    k_t, k_l = weights.keys_for(2**31 + 5, cfg["num_hidden_layers"])
    for l in range(cfg["num_hidden_layers"]):
        one = fam_weights.layer(cfg, fmt, k_l[l])
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda x: x[l], tree["blocks"]))):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    other = program.served_tree(cfg, fmt, 6, EMIT)
    assert not np.array_equal(np.asarray(tree["embed"]), np.asarray(other["embed"]))


@pytest.mark.parametrize("name", ["mistral-7b-int8", "smollm2-1.7b-bf16"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails_and_reference_passes(name, seed):
    cfg = _tiny(name)
    fmt, how = cfg["serving"]["weights"], cfg["control"]["weights"]
    limit = spec.load_json("cells", {
        "mistral-7b-int8": "mistral-7b-int8.explain",
        "smollm2-1.7b-bf16": "smollm2-1.7b-bf16.explain"}[name] + ".json")[
            "rehearsal"]["max_logit_gap_limit"]
    rng = np.random.default_rng(seed)
    rows, width, n_out = 2, 96, 24
    samples = []
    for _ in range(rows):  # the reference's own greedy continuation
        prompt = [int(t) for t in rng.choice(EMIT, size=40)]
        out = []
        for _ in range(n_out):
            toks = np.zeros((1, width), np.int32)
            seq = prompt + out
            toks[0, :len(seq)] = seq
            lg = fam_reference.logits_at(cfg, fmt, seed, EMIT, toks,
                                     np.array([[len(seq) - 1]]))
            out.append(int(jnp.argmax(lg[0, 0])))
        samples.append((prompt, out))
    sound = reference.max_logit_gap(cfg, fmt, seed, EMIT, samples, rows, width, n_out)
    assert sound["max_logit_gap"] <= limit and sound["tokens_compared"] == rows * n_out
    low = reference.max_logit_gap(cfg, fmt, seed, EMIT, samples, rows, width,
                                  n_out, control=how)
    assert low["max_logit_gap"] > limit, low
    # ... and a served token altered where it is produced is seen too.
    bad = [(p, o[:5] + [EMIT[(o[5] + 1) % len(EMIT)]] + o[6:]) for p, o in samples]
    alt = reference.max_logit_gap(cfg, fmt, seed, EMIT, bad, rows, width, n_out)
    assert alt["max_logit_gap"] > limit

"""The control comes out not correct: the plain reference computed in the
precision below the configuration's, put in the program's place, reads
above the committed limits.  At a size a test run holds; the chip
readings the limits were set from are in PERF.md."""
import numpy as np
import pytest

from bench.drivers import lm, mlda
from bench.tests import small


@pytest.fixture(scope="module")
def uq():
    import jax.numpy as jnp

    cfg = small.mlda_config()
    ref = mlda.Reference(cfg)
    return cfg, ref, mlda.Reference(cfg, jnp.bfloat16, share=ref)


class Rows:
    """Compared rows as a run would draw them (the program's outputs are
    not used: the control stands in its place)."""

    def __init__(self, thetas, logp=True):
        self.rows = {lvl: (thetas, None) for lvl in (0, 1, 2)}
        self.logp = (thetas, None) if logp else None


def test_uq_control_fails_every_number(uq):
    cfg, ref, control = uq
    thetas = np.random.default_rng(0).uniform(-100, 100, (8, 2))
    readings = mlda.compare(cfg, Rows(thetas), ref, control=control)
    limits = small.load("configs", "tohoku-mlda-paper")["limits"]
    assert set(readings) == set(limits)
    for name, value in readings.items():
        assert value > limits[name], name


def test_uq_reference_passes_itself(uq):
    cfg, ref, _ = uq
    thetas = np.random.default_rng(1).uniform(-100, 100, (8, 2))
    readings = mlda.compare(cfg, Rows(thetas), ref, control=ref)
    assert all(v == 0.0 for v in readings.values())


def test_lm_control_fails():
    """The served configuration as it is run (bf16 weights, published
    widths and depth) on one 32-token prompt; the tokens are the
    reference's own greedy picks, as a sound program would serve them."""
    import jax.numpy as jnp

    from bench.reference import qwen2

    cfg = small.load("configs", "qwen2-0.5b")
    params = lm.make_weights(cfg, 11)
    key = tuple(sorted(lm.model_sizes(cfg).items()))
    prompt = np.random.default_rng(0).integers(0, cfg["vocab_size"], size=32).astype(np.int32)
    seq = list(prompt)
    for _ in range(16):
        padded = np.zeros(64, np.int32)
        padded[: len(seq)] = seq
        seq.append(int(jnp.argmax(qwen2.logits(params, jnp.asarray(padded), cfg=key)[len(seq) - 1])))
    sched = type("Sched", (), {"prompts": [prompt]})()
    picked = [(0, np.asarray(seq[32:]))]
    limit = cfg["limits"]["served_logit_gap"]
    assert lm.widest_gap(params, cfg, sched, picked, 64) == 0.0
    assert lm.widest_gap(params, cfg, sched, picked, 64, mode="fp8") > limit

"""The control, at a size a test run holds: the plain reference computed
with float8 (e4m3) matmul operands, the nearest precision below the
configurations' bfloat16, in the program's place.  At least one compared
number must read above its limit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model
from bench.drivers import train

TEST_SIZE = dict(hidden_size=256, intermediate_size=1024, num_attention_heads=4,
                 num_hidden_layers=2, vocab_size=2048)


def test_serve_control_fails():
    conf = model.load_config("internlm2-1.8b-serve")
    conf.update(TEST_SIZE, num_key_value_heads=2)
    ref = model.reference(conf)
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, conf["vocab_size"], (4, 256)), jnp.int32)
    valid = jnp.ones((4, 256), bool).at[:, :64].set(False)
    hc = ref.serve_hidden(conf, 11, tokens, "fp8")
    gaps = ref.served_gaps(conf, 11, tokens, jnp.zeros_like(tokens), valid, control_hidden=hc)
    assert float(jnp.max(gaps)) > conf["limits"]["max_logit_gap"]


def test_train_control_fails():
    conf = model.load_config("olmo-1b-train")
    conf.update(TEST_SIZE, num_key_value_heads=4)
    ref = model.reference(conf)
    rng = np.random.default_rng(6)
    batches = [(rng.integers(0, 2048, (4, 128)).astype(np.int32),
                rng.integers(0, 2048, (4, 128)).astype(np.int32)) for _ in range(3)]
    program = ref.train_reference(conf, 11, batches, "fp8")
    reference = ref.train_reference(conf, 11, batches, "f32")
    checks = train.compare(conf, *program, *reference)
    assert any(v > lim for v, lim in checks.values()), checks

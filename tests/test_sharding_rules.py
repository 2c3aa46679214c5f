"""Shape-aware logical sharding resolution (pure logic, no devices needed
beyond the local mesh)."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.models.sharding import (
    DEFAULT_RULES,
    DP_RULES,
    serving_spec,
    spec,
    use_mesh,
    zero1_axes,
)


class FakeMesh:
    """Duck-typed mesh: (data=16, model=16)."""

    axis_names = ("data", "model")

    class _Dev:
        shape = (16, 16)

    devices = _Dev()
    size = 256


MESH = FakeMesh()


def _spec(names, shape, rules=DEFAULT_RULES):
    return spec(names, rules=rules, mesh=MESH, shape=shape)


def test_divisible_dims_shard():
    assert _spec((None, "mlp"), (4096, 12800)) == P(None, "model")
    assert _spec(("vocab", None), (49168, 4096)) == P("model", None)


def test_non_divisible_dims_fall_back_to_replicated():
    # 92553 % 16 != 0 -> no vocab sharding
    assert _spec(("vocab", None), (92553, 2048)) == P(None, None)


def test_conflict_resolution_first_dim_wins():
    # llama4 expert weights: experts take "data"; expert_embed would also
    # want "data" -> dropped; expert_mlp gets "model"
    s = _spec(("layers", "experts", "expert_embed", "expert_mlp"), (24, 128, 5120, 8192))
    assert s == P(None, "data", None, "model")


def test_grok_virtual_expert_fallback():
    # 8 experts cannot shard 16-way -> d_model picks up "data" (2D expert TP)
    s = _spec(("layers", "experts", "expert_embed", "expert_mlp"), (64, 8, 6144, 32768))
    assert s == P(None, None, "data", "model")


def test_tuple_axes_degrade_to_prefix():
    # batch 8 with ("pod","data") on a single-pod mesh -> "data" (8 % 16 != 0
    # fails, but there is no pod axis so candidates = ("data",) and 8 % 16
    # fails -> None)
    assert _spec(("batch", None), (8, 128)) == P(None, None)
    assert _spec(("batch", None), (256, 128)) == P("data", None)


def test_dp_rules_put_everything_on_batch():
    assert _spec(("batch", None, None), (256, 4096, 2048), rules=DP_RULES) == P(
        ("data", "model"), None, None
    )
    assert _spec((None, "mlp"), (2048, 8192), rules=DP_RULES) == P(None, None)


def test_zero1_axes_targets_first_replicated_dim():
    assert zero1_axes(("layers", None, "mlp")) == ("layers", "zero", "mlp")
    assert zero1_axes(("vocab", None)) == ("vocab", "zero")
    # fully-sharded params gain nothing
    assert zero1_axes(("layers", "experts", "expert_embed", "expert_mlp")) == (
        "layers", "experts", "expert_embed", "expert_mlp",
    )


def test_without_shape_no_filtering():
    assert spec(("vocab",), rules=DEFAULT_RULES, mesh=MESH) == P("model")


@pytest.mark.parametrize("names, shape, want", [
    # granite-3-8b's layer stack on the 16-wide model axis: columns stay split
    (("layers", None, "heads"), (40, 4096, 4096), P(None, None, "model")),
    (("layers", None, "mlp"), (40, 4096, 12800), P(None, None, "model")),
    # wo and w_down: DEFAULT_RULES splits their contracting rows; serving
    # moves the split to their columns
    (("layers", "heads", None), (40, 4096, 4096), P(None, None, "model")),
    (("layers", "mlp", None), (40, 12800, 4096), P(None, None, "model")),
    # columns the axis does not divide: the matrix stays whole
    (("layers", "mlp", None), (40, 12800, 4100), P(None, None, None)),
    # embedding, head and norms keep DEFAULT_RULES' layout
    (("vocab", None), (49168, 4096), P("model", None)),
    ((None, "vocab"), (4096, 49168), P(None, "model")),
    (("layers", None), (40, 4096), P(None, None)),
])
def test_serving_spec_splits_layer_matrices_by_columns(names, shape, want):
    assert serving_spec(names, shape, MESH) == want

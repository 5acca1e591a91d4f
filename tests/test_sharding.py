"""Sharding policy resolution: rule precedence, divisibility fallback,
duplicate-axis dedup, leaf-path mapping (params, optimizer state, caches)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import model
from repro.train import TrainHParams, init_state


class FakeMesh:
    """Only .shape is consulted by ShardingPolicy.spec."""

    def __init__(self, **axes):
        self.shape = dict(axes)


POL = shd.ShardingPolicy(FakeMesh(data=16, model=16), shd.TRAIN_RULES)
POL_POD = shd.ShardingPolicy(
    FakeMesh(pod=2, data=16, model=16), shd.TRAIN_RULES
)
POL_SERVE = shd.ShardingPolicy(FakeMesh(data=16, model=16), shd.SERVE_RULES)


def test_batch_spans_pod_and_data_on_multipod():
    assert POL_POD.spec(("batch", "seq"), (256, 4096)) == P(("pod", "data"))
    assert POL.spec(("batch", "seq"), (256, 4096)) == P("data")


def test_divisibility_fallback_replicates():
    # 9 heads cannot shard over 16 -> replicated
    assert POL.spec(("fsdp", "heads", "head_dim"), (576, 9, 64)) == P("data")
    # 64 heads can
    assert POL.spec(("fsdp", "heads", "head_dim"), (8192, 64, 128)) == P(
        "data", "model"
    )


def test_duplicate_mesh_axis_dedup():
    # expert takes model; ffn would also want model -> falls to None
    spec = POL.spec(("expert", "fsdp", "ffn"), (16, 8192, 24576))
    assert spec == P("model", "data")
    # 60 experts don't divide 16 -> expert drops, ffn gets model
    spec = POL.spec(("expert", "fsdp", "ffn"), (60, 2048, 1408))
    assert spec == P(None, "data", "model")


def test_serve_rules_differ_from_train():
    # weights are not FSDP-sharded when serving
    assert POL_SERVE.spec(("fsdp", "ffn"), (4096, 14336)) == P(None, "model")
    # decode cache seq dim shards over model (SP)
    assert POL_SERVE.spec(
        ("batch", "kv_seq", "kv_heads", "head_dim"), (128, 32768, 8, 128)
    ) == P("data", "model")


def test_rule_override():
    rules = shd.AxisRules(shd.SERVE_RULES).override(
        kv_seq=("data", "model")
    )
    pol = shd.ShardingPolicy(FakeMesh(data=16, model=16), rules)
    spec = pol.spec(("batch", "kv_seq"), (1, 524288))
    # batch=1 unshardable; kv_seq takes both axes
    assert spec == P(None, ("data", "model"))


def _real_policy(rules=shd.TRAIN_RULES):
    mesh = make_host_mesh(1, 1)
    return shd.ShardingPolicy(mesh, rules)


def test_leaf_logical_param_paths():
    cfg = get_config("mixtral-8x7b", smoke=True)
    params = jax.eval_shape(lambda: model.init_params(jax.random.key(0), cfg))
    shardings = shd.param_specs(params, _real_policy())
    flat = dict(
        (jax.tree_util.keystr(p), s)
        for p, s in jax.tree_util.tree_flatten_with_path(shardings)[0]
    )
    # stacked period weights get a leading replicated (periods) dim
    wq = [v for k, v in flat.items() if "wq" in k][0]
    assert wq.spec[0] is None  # periods axis replicated
    emb = [v for k, v in flat.items() if k == "['embed']"][0]
    assert emb.spec == P("model", "data")  # vocab x fsdp


def test_optimizer_state_specs_follow_params():
    cfg = get_config("deepseek-67b", smoke=True)  # adafactor
    hp = TrainHParams()
    state = jax.eval_shape(
        lambda: init_state(jax.random.key(0), cfg, hp)
    )
    shardings = shd.state_specs(state, _real_policy())
    flat = dict(
        (jax.tree_util.keystr(p), s)
        for p, s in jax.tree_util.tree_flatten_with_path(shardings)[0]
    )
    # adafactor factored stats: vr drops the last axis of the param spec
    vr = [v for k, v in flat.items() if "w_gate" in k and "vr" in k]
    vc = [v for k, v in flat.items() if "w_gate" in k and "vc" in k]
    assert vr and vc


def test_constrain_noop_without_policy():
    x = jnp.zeros((4, 4))
    assert shd.constrain(x, ("batch", "seq")) is x


def test_constrain_applies_on_real_mesh():
    mesh = make_host_mesh(1, 1)
    pol = shd.ShardingPolicy(mesh, shd.TRAIN_RULES)
    with shd.use_policy(pol):
        y = jax.jit(lambda x: shd.constrain(x, ("batch", "seq")))(
            jnp.ones((4, 4))
        )
    assert y.shape == (4, 4)


def test_tree_specs_unknown_leaves_replicate():
    tree = {"mystery": jax.ShapeDtypeStruct((3, 5), jnp.float32)}
    specs = shd.tree_logical_specs(tree, _real_policy(), shd.PARAM_AXES)
    assert specs["mystery"].spec == P()

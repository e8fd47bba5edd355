"""Sharding-rule sanity on an AbstractMesh (no fake devices needed):
every param leaf of every arch gets a legal PartitionSpec (divisibility
respected), batch/pod axes behave, decode caches shard B/data + C/model."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.configs.shapes import SHAPES
from repro.launch import shardings as sh
from repro.models.model import Model

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(shape, spec, axis_sizes):
    for dim, entry in zip(shape, spec):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else entry
        total = 1
        for a in axes:
            total *= axis_sizes[a]
        assert dim % total == 0, f"{shape} {spec}: {dim} % {total}"


@pytest.mark.parametrize("arch", list_archs())
def test_param_specs_legal(arch):
    cfg = get_config(arch)
    model = Model(cfg)
    shapes = model.param_specs()
    axis_sizes = {"data": 16, "model": 16}

    def one(path, leaf):
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        spec = sh.param_spec(MESH, pstr, leaf.shape)
        assert len(spec) <= len(leaf.shape)
        padded = tuple(spec) + (None,) * (len(leaf.shape) - len(spec))
        _check_divisible(leaf.shape, padded, axis_sizes)
        return spec
    jax.tree_util.tree_map_with_path(one, shapes)


@pytest.mark.parametrize("arch", ["glm4-9b", "mixtral-8x7b",
                                  "qwen2-vl-72b", "granite-34b"])
def test_big_matrices_are_2d_sharded(arch):
    """FSDP x TP: the large weights must shard on BOTH mesh axes."""
    cfg = get_config(arch)
    model = Model(cfg)
    shapes = model.param_specs()
    found_2d = []

    def one(path, leaf):
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        spec = sh.param_spec(MESH, pstr, leaf.shape)
        axes = {a for e in spec if e is not None
                for a in ((e,) if isinstance(e, str) else e)}
        if {"data", "model"} <= axes:
            found_2d.append(pstr)
    jax.tree_util.tree_map_with_path(one, shapes)
    assert len(found_2d) >= 3, f"{arch}: too few 2D-sharded weights"


def test_batch_spec_pod_axis():
    spec = sh.batch_spec(MESH3, 2)
    assert spec[0] == ("pod", "data")
    spec1 = sh.batch_spec(MESH, 2)
    assert spec1[0] in ("data", ("data",))  # P() normalizes 1-tuples


@pytest.mark.parametrize("arch,shape", [("glm4-9b", "decode_32k"),
                                        ("granite-34b", "decode_32k"),
                                        ("zamba2-2.7b", "long_500k")])
def test_decode_cache_shardings(arch, shape):
    cfg = get_config(arch)
    model = Model(cfg)
    spec = SHAPES[shape]
    specs = model.input_specs(spec)
    state_shape = specs["state"]
    shd = sh.decode_state_shardings(MESH, state_shape, cfg)

    def check(path, leaf):
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        s = jax.tree.leaves(
            shd, is_leaf=lambda x: hasattr(x, "spec"))
    k_shd = shd["kv"]["k"].spec if "kv" in shd else None
    if k_shd is not None:
        l, b, c, kv, d = jax.tree.leaves(
            state_shape["kv"]["k"], is_leaf=lambda x: hasattr(x, "shape")
        )[0].shape
        if b % 16 == 0:
            assert k_shd[1] == "data"
        if c % 16 == 0:
            assert k_shd[2] == "model"


def test_per_device_bytes_fit_hbm():
    """Analytic arg budget (params+opt or params+cache) must fit 16 GiB
    on the single-pod mesh for the heaviest cells."""
    import json
    import glob
    import os
    recs = []
    for f in glob.glob("experiments/dryrun/*_pod256.json"):
        with open(f) as fh:
            recs.append(json.load(fh))
    if not recs:
        pytest.skip("dry-run artifacts not present")
    for r in recs:
        if "arg_bytes_per_device_analytic" not in r:
            continue
        gib = r["arg_bytes_per_device_analytic"] / 2 ** 30
        assert gib < 16.0, f"{r['cell']}: {gib:.1f} GiB/device args"

"""Launch-layer units: collective parsing, mesh construction, config
registry completeness — cheap tests that guard the dry-run tooling."""
from __future__ import annotations

import os

import numpy as np
import pytest

from repro.launch.dryrun import parse_collective_bytes, _shape_bytes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_collective_bytes():
    hlo = """
  %ag = bf16[2,128]{1,0} all-gather(%x), replica_groups={}
  %ar = f32[64]{0} all-reduce(%y), to_apply=%sum
  %rs = f32[8,8]{1,0} reduce-scatter(%z), dimensions={0}
  %a2a = s32[16]{0} all-to-all(%w)
  %cp = pred[32]{0} collective-permute(%v)
  %plain = f32[100]{0} add(%a, %b)
"""
    out = parse_collective_bytes(hlo)
    assert out["per_type_bytes"]["all-gather"] == 2 * 128 * 2
    assert out["per_type_bytes"]["all-reduce"] == 64 * 4
    assert out["per_type_bytes"]["reduce-scatter"] == 64 * 4
    assert out["per_type_bytes"]["all-to-all"] == 16 * 4
    assert out["per_type_bytes"]["collective-permute"] == 32
    assert out["total_bytes"] == sum(out["per_type_bytes"].values())
    assert out["counts"]["all-gather"] == 1


def test_shape_bytes_scalars_and_dtypes():
    assert _shape_bytes("f32", "") == 4          # scalar
    assert _shape_bytes("bf16", "4,4") == 32
    assert _shape_bytes("pred", "8") == 8
    assert _shape_bytes("s8", "3,3") == 9


def test_registry_covers_all_assigned_archs():
    from repro.configs import common as cc
    assert len(cc.ALL_ARCHS) == 10
    for arch in cc.ALL_ARCHS:
        mod = cc.get_arch(arch)
        assert mod.ARCH_ID == arch
        assert len(mod.SHAPES) == 4
        assert mod.model_config() is not None
        assert mod.reduced_config() is not None


def test_lm_param_specs_match_param_shapes():
    """v1 and v2 spec pytrees must be structurally compatible with the
    parameter pytrees for every LM arch (guards sharding/shape drift)."""
    import jax
    from repro.configs import common as cc
    from repro.models import transformer as tfm
    for arch in ("gemma2-9b", "minitron-4b", "granite-8b",
                 "deepseek-v2-lite-16b", "mixtral-8x22b"):
        cfg = cc.get_arch(arch).model_config()
        shapes = tfm.param_shapes(cfg)
        for scheme in ("v1", "v2"):
            specs = tfm.param_specs(cfg, pod=False, scheme=scheme)
            def check(sh, sp):
                assert len(sp) <= len(sh.shape), (arch, scheme, sh, sp)
            jax.tree.map(check, shapes, specs,
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
                         or hasattr(x, "_partitions"))


@pytest.mark.parametrize("env_dir", [None, "custom"])
def test_compile_cache_placement(tmp_path, monkeypatch, env_dir):
    """The entry points' compile cache lands in $JAX_COMPILATION_CACHE_DIR
    when it is set, and otherwise in the checkout's fixed, gitignored
    `.jax_cache/` — never a path built per run."""
    import jax
    from repro.launch import compile_cache

    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV, want)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()

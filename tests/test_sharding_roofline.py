"""Sharding rules + roofline parsing (host-side; no 512-device mesh here —
the full mesh is exercised by launch/dryrun.py in a separate process)."""

import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.models import build_model
from repro.roofline.analysis import (collective_bytes_per_device,
                                     model_flops, parse_collectives,
                                     roofline_terms)
from repro.sharding.partition import batch_spec, param_shardings


def mesh1():
    return jax.make_mesh((1, 1), ("data", "model"))


class TestPartitionRules:
    def test_every_spec_divides(self):
        """On a (1,1) mesh every rule must produce valid shardings for
        every arch (divisibility fallback never crashes)."""
        mesh = mesh1()
        for arch in ("qwen2-1.5b", "hymba-1.5b", "rwkv6-7b",
                     "mixtral-8x22b", "seamless-m4t-large-v2"):
            cfg = get_config(arch)
            shapes = build_model(cfg).abstract_params()
            sh = param_shardings(shapes, mesh, cfg, fsdp=True)
            assert jax.tree_util.tree_structure(sh) == \
                jax.tree_util.tree_structure(shapes)

    def test_batch_spec_fallbacks(self):
        mesh = mesh1()
        assert batch_spec(mesh, 4) == P(("data",), None)
        # batch=1 on a (data=1) mesh still divides
        assert batch_spec(mesh, 1) == P(("data",), None)


class TestHloParsing:
    HLO = """
  %all-reduce.1 = f32[16,4096]{1,0} all-reduce(%x), replica_groups={}
  %all-gather.2 = bf16[8,1024,128]{2,1,0} all-gather(%y), dimensions={1}
  %rs = f32[4,256]{1,0} reduce-scatter(%z), dimensions={0}
  %a2a = (f32[2,2]{1,0}, f32[2,2]{1,0}) all-to-all(%p, %q)
  %notacoll = f32[2,2]{1,0} add(%a, %b)
"""

    def test_parse_kinds_and_bytes(self):
        got = dict()
        for kind, b in parse_collectives(self.HLO):
            got.setdefault(kind, 0)
            got[kind] += b
        assert got["all-reduce"] == 16 * 4096 * 4
        assert got["all-gather"] == 8 * 1024 * 128 * 2
        assert got["reduce-scatter"] == 4 * 256 * 4
        assert got["all-to-all"] == 2 * (2 * 2 * 4)

    def test_traffic_weighting(self):
        per = collective_bytes_per_device(self.HLO)
        assert per["all-reduce"] == 2.0 * 16 * 4096 * 4

    def test_roofline_terms_math(self):
        cost = {"flops": 197e12, "bytes accessed": 819e9}
        t = roofline_terms(cost, self.HLO, chips=256, model_flops=197e12)
        assert t.compute_s == pytest.approx(1.0)
        assert t.memory_s == pytest.approx(1.0)
        assert t.dominant in ("compute", "memory")
        assert t.hlo_flops == pytest.approx(197e12 * 256)


class TestModelFlops:
    def test_moe_uses_active_params(self):
        from repro.configs.base import SHAPES
        dense = get_config("command-r-plus-104b")
        moe = get_config("dbrx-132b")
        shp = SHAPES["train_4k"]
        assert model_flops(moe, shp) < 0.5 * moe.param_count() * 6 * \
            shp.global_batch * shp.seq_len
        assert model_flops(dense, shp) == pytest.approx(
            6.0 * dense.param_count() * shp.global_batch * shp.seq_len)


@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    """End-to-end dry-run of one small cell on the 512-device mesh, in a
    subprocess (keeps this process on the 1-device backend)."""
    code = (
        "from repro.launch.dryrun import run_cell\n"
        "r = run_cell('qwen2-1.5b', 'decode_32k', 'single', verbose=False)\n"
        "assert r['status'] == 'ok', r.get('error')\n"
        "assert r['roofline']['hlo_flops'] > 0\n"
        "print('CELL-OK')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=1200,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert "CELL-OK" in out.stdout, out.stderr[-2000:]

"""Distribution-layer tests.

The heavyweight 512-device dry-run is exercised by ``repro.launch.dryrun``
(results under experiments/dryrun/).  Here we test the machinery on small
meshes in a subprocess (device count must be set before jax init):
lower+compile for each family incl. train/prefill/decode, sharding-rule
mapping, and the HLO cost analyzer against hand-computable modules.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo_cost import analyze_hlo
from repro.dist.sharding import (SINGLE_POD_RULES, axes_to_spec,
                                 is_axes, with_overrides)

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------------------------
# axis rules (pure)
# --------------------------------------------------------------------------

def test_axes_to_spec_mapping():
    r = SINGLE_POD_RULES
    spec = axes_to_spec(("batch", "act_seq", None), r)
    assert tuple(spec) == ("data", None, None)
    spec = axes_to_spec(("layers", "fsdp", "tp"), r)
    assert tuple(spec) == (None, "data", "model")
    sp = with_overrides(r, act_seq="model")
    assert tuple(axes_to_spec(("batch", "act_seq", None), sp)) == (
        "data", "model", None)


def test_is_axes_leaf_predicate():
    from repro.models.ssm import SSMCache
    assert is_axes(("batch", None))
    assert is_axes(())
    assert not is_axes(SSMCache(("a",), ("b",), ("c",), ("d",)))  # NamedTuple
    assert not is_axes(({"k": 1},))


# --------------------------------------------------------------------------
# HLO cost analyzer (single device, hand-computable)
# --------------------------------------------------------------------------

def test_hlo_cost_counts_scan_trips():
    n = 128
    S = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)

    def g(h, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(body, h, None, length=7)[0]

    r = analyze_hlo(jax.jit(g).lower(S((n, n)), S((n, n))).compile().as_text())
    assert abs(r["flops"] / (7 * 2 * n ** 3) - 1.0) < 1e-6


def test_hlo_cost_counts_remat_factor():
    n = 128
    S = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)

    def loss(h, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        return jnp.sum(jax.lax.scan(jax.checkpoint(body), h, None,
                                    length=10)[0] ** 2)

    r = analyze_hlo(jax.jit(jax.grad(loss, argnums=1))
                    .lower(S((n, n)), S((n, n))).compile().as_text())
    assert abs(r["flops"] / (4 * 10 * 2 * n ** 3) - 1.0) < 0.01  # 4/3 * 3x


# --------------------------------------------------------------------------
# small-mesh lowering in a subprocess (needs >1 device before jax init)
# --------------------------------------------------------------------------

_SUBPROC = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys; sys.path.insert(0, "src")
    from repro.configs import get_smoke
    from repro.configs.base import ShapeCell
    from repro.dist.sharding import make_compat_mesh
    from repro.launch.dryrun import lower_cell

    mesh = make_compat_mesh((4, 4), ("data", "model"))
    out = {}
    for name in %(archs)s:
        cfg = get_smoke(name)
        for cell in [ShapeCell("t", 64, 8, "train"),
                     ShapeCell("d", 64, 8, "decode")]:
            rec = lower_cell(cfg, cell, mesh)
            out[f"{name}/{cell.name}"] = {
                "flops": rec["hlo_cost"]["flops"],
                "coll": rec["collectives"]["total"],
            }
    print(json.dumps(out))
""")


_SP_SUBPROC = textwrap.dedent("""
    import os, json, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys; sys.path.insert(0, "src")
    import jax
    from repro.configs import get_smoke
    from repro.configs.base import ShapeCell
    from repro.dist.sharding import make_compat_mesh, use_rules
    from repro.launch import input_specs as specs_mod
    from repro.launch.mesh import rules_for
    from repro.models import registry

    mesh = make_compat_mesh((4, 4), ("data", "model"))
    cfg = get_smoke("tinyllama-1.1b")
    cell = ShapeCell("t", 16, 8, "train")
    fns = registry.build(cfg, tp=mesh.shape["model"])
    params_s = specs_mod.params_specs(cfg, mesh.shape["model"])
    batch_s = specs_mod.batch_specs(cfg, cell)

    # the (batch, seq, d_model) activation annotations in the lowered HLO:
    # shard(h, "batch", "act_seq", None) constraints on 8x16x64 tensors,
    # printed as one mesh-axis set per dim: {"data"}, {"model"}, {}
    pat = re.compile(r'sdy\\.sharding_constraint %\\d+ <@mesh, \\[([^\\]]*)\\]> '
                     r': tensor<8x16x64x')

    def act_shardings(sp):
        rules = rules_for(mesh, global_batch=cell.global_batch,
                          sequence_parallel=sp)
        fresh = lambda p, b: fns.loss(p, b)  # defeat jax's trace cache:
        # ambient rules are invisible to its key, so reusing the same
        # function object would replay the other variant's trace
        with use_rules(rules):
            txt = jax.jit(fresh).lower(params_s, batch_s).as_text()
        return rules.rules["act_seq"], pat.findall(txt)

    sp_rule, sp_sh = act_shardings(True)
    base_rule, base_sh = act_shardings(False)
    print(json.dumps({"sp_rule": sp_rule, "base_rule": base_rule,
                      "sp_shardings": sp_sh, "base_shardings": base_sh}))
""")


def test_sequence_parallel_lowers_act_seq_to_model():
    """ROADMAP open item: ``rules_for(..., sequence_parallel=True)`` must
    map ``act_seq -> model`` all the way into the jitted HLO of a token
    arch — the (batch, seq, d) activations are constrained to batch over
    ``data`` and seq over ``model``, and seq is unsharded without sp."""
    proc = subprocess.run(
        [sys.executable, "-c", _SP_SUBPROC], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sp_rule"] == "model" and out["base_rule"] is None
    assert out["sp_shardings"], "no act_seq annotations found in the HLO"
    assert all(s == '{"data"}, {"model"}, {}' for s in out["sp_shardings"])
    # without sequence_parallel the seq dim stays unsharded (replicated
    # across the model axis): only batch is split, over data
    assert out["base_shardings"], "baseline act annotations vanished"
    assert all(s == '{"data"}, {}, {}' for s in out["base_shardings"])


@pytest.mark.parametrize("archs", [
    ["tinyllama-1.1b", "phi3.5-moe-42b-a6.6b"],
    ["mamba2-1.3b", "seamless-m4t-large-v2"],
])
def test_small_mesh_lower_compile(archs):
    code = _SUBPROC % {"archs": repr(archs)}
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))), timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for k, v in out.items():
        assert v["flops"] > 0, k
        assert v["coll"] > 0, k  # sharded execution must communicate

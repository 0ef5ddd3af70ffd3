"""The expert-share cell and its reference at a small size on the CPU.

* ``moe_dcra`` told it holds a share of the experts, with the shared
  expert, computes what ``bench/ref/moe_share.py`` computes for that share;
* the routed parts of every share, with the shared expert counted once,
  add up to the reference's uncut layer;
* through the harness's own run (the look for a chip is skipped), the
  program comes out correct, and each control of
  ``bench/control_share.py`` and each fault of ``bench/control.py`` does
  not.
"""
import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, control_share
from bench import run as bench_run
from bench.ref import moe_share as ref

CELL = "deepseek-v3-moe-prefill"
# D = 64, 32 experts in 8 groups, top-4 of the best 4 groups, 8 held, 2 layers
SMALL = {"hidden_size": 64, "moe_intermediate_size": 32,
         "router_experts": 32, "n_routed_experts": 8,
         "num_experts_per_tok": 4, "n_group": 8, "topk_group": 4,
         "num_hidden_layers": 2}
SMALL_TRAFFIC = {"batch": 2, "seq": 64, "ref_block": 64}
SEED = 2**31 + 77
D, E, F, FS, K, HELD = 64, 32, 32, 48, 4, 8


def _layer(first, held):
    return ref.Layer(n_experts=E, first=first, held=held, top_k=K, n_group=8,
                     topk_group=4, scaling=2.5, eps=1e-6)


def _program(n_shared=1):
    from repro.configs import get_config
    from repro.configs.base import MoEConfig
    from repro.core import dispatch
    from repro.core.compat import make_mesh
    arch = dataclasses.replace(get_config("olmoe-1b-7b").reduced(), d_model=D,
                               moe=MoEConfig(
                                   num_experts=E, top_k=K, d_expert=F,
                                   capacity_factor=8.0, scoring="sigmoid",
                                   n_group=8, topk_group=4,
                                   routed_scaling_factor=2.5,
                                   n_shared=n_shared, d_shared=FS))
    mesh = make_mesh((1, 1, 1), ("data", "expert", "tp"))

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def layer(params, x, first, held):
        info = dispatch.MeshInfo(mesh, pod_axis=None,
                                 expert_share=(first, held))
        with jax.default_matmul_precision("highest"):
            return dispatch.moe_dcra(params, x, arch, info)[0]
    return layer


@functools.partial(jax.jit, static_argnums=2)
def _ref_moe(params, x, layer):
    with jax.default_matmul_precision("highest"):
        return ref.moe(params, x.reshape(-1, D), layer)


def _params():
    p = ref.init_params(jax.random.key(5), D, E, E, F, FS, bias_std=0.05)
    return {k: v.astype(jnp.float32) for k, v in p.items()}


def _share(params, first, held):
    return dict(params, **{k: params[k][first:first + held]
                           for k in ("wg", "wu", "wd")})


def test_share_with_shared_expert_matches_reference():
    params = _params()
    x = jax.random.normal(jax.random.key(6), (2, 64, D))
    got = _program()(_share(params, 8, HELD), x, 8, HELD)
    want, _, held = _ref_moe(_share(params, 8, HELD), x, _layer(8, HELD))
    assert int(held.sum()) > 0
    np.testing.assert_allclose(np.asarray(got).reshape(-1, D),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def test_routed_parts_of_every_share_add_up_to_the_uncut_layer():
    params = _params()
    x = jax.random.normal(jax.random.key(7), (2, 64, D))
    routed = _program(n_shared=0)
    parts = sum(routed(_share(params, s, HELD), x, s, HELD)
                for s in range(0, E, HELD))
    from repro.core.dispatch import shared_expert
    with jax.default_matmul_precision("highest"):
        got = parts + shared_expert(params, x)
    want, _, held = _ref_moe(params, x, _layer(0, E))
    assert np.all(np.asarray(held) == K)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, D),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def _run(patch=None):
    spec = bench_run.load_spec(CELL)
    spec["config"].update(SMALL)
    spec["traffic"].update(SMALL_TRAFFIC)
    with patch() if patch else contextlib.nullcontext():
        return bench_run.run_cell(spec, SEED, 0.3, False, jax.devices())


def test_program_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["timed_vs_layers_max"]["value"] == 0.0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("ctl", control_share.CONTROLS,
                         ids=lambda c: c.__name__)
def test_control_is_not_correct(ctl):
    res = _run(ctl)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", control.FAULTS["moe"],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault):
    res = _run(lambda: fault("moe"))
    assert not res["correct"], res["checks"]

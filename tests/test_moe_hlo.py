"""OLMoE's ``moe_dcra`` lowers to the HLO it had before expert shares,
sigmoid routing and shared experts were added (CPU).

The layer is lowered, not run, from shapes: at the widths of the
benchmark's ``olmoe-1b-7b-moe`` configuration on one device, and at the
reduced width on 8 fake devices for each packaging the dispatch plan can
pick (fused tp, tp-sharded FFN, two-stage over pods). Each case keeps two
SHA-256 digests: of the HLO text without debug information (the
computation) and of its ``op_name`` metadata in order (the device scopes
a profile reads). Source file and line information is left out, so an
edit that moves code without changing what it lowers to passes.

Regenerate (only when the OLMoE layer is meant to change)::

    PYTHONPATH=src python tests/test_moe_hlo.py --regen
"""
import json
import os
import subprocess
import sys

import pytest

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "moe_dcra_hlo.json")

SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import dataclasses, hashlib, json, re
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.compat import make_mesh
from repro.core.dispatch import MeshInfo, moe_dcra
from repro.models.moe import init_moe


def digests(cfg, info, x_shape, dtype, w_dtype):
    shapes = jax.eval_shape(lambda: init_moe(jax.random.key(0), cfg))
    params = {k: jax.ShapeDtypeStruct(
        v.shape, w_dtype if k in ('wg', 'wu', 'wd') else jnp.float32)
        for k, v in shapes.items()}
    x = jax.ShapeDtypeStruct(x_shape, dtype)

    def moe_layer(p, x):
        with jax.default_matmul_precision('highest'):
            return moe_dcra(p, x, cfg, info)[0]

    lowered = jax.jit(moe_layer).lower(params, x)
    plain = lowered.as_text(dialect='hlo')
    names = re.findall(r'op_name="([^"]*)"',
                       lowered.as_text(dialect='hlo', debug_info=True))
    return {'hlo': hashlib.sha256(plain.encode()).hexdigest(),
            'op_names': hashlib.sha256('\n'.join(names).encode()).hexdigest(),
            'hlo_chars': len(plain), 'op_name_count': len(names)}


res = {}
bench = get_config('olmoe-1b-7b')
bench = dataclasses.replace(bench, moe=dataclasses.replace(
    bench.moe, capacity_factor=2.0))
one = MeshInfo(make_mesh((1, 1, 1), ('data', 'expert', 'tp')), pod_axis=None)
res['bench_one_device'] = digests(bench, one, (8, 512, 2048), jnp.bfloat16,
                                  jnp.bfloat16)

cfg = get_config('olmoe-1b-7b').reduced()
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=8.0))
cfg8 = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                        num_experts=8))
mesh = make_mesh((2, 2, 2), ('data', 'expert', 'tp'))
mesh2 = make_mesh((2, 1, 2, 2), ('pod', 'data', 'expert', 'tp'))
shape = (4, 16, cfg.d_model)
res['single_pod_fused'] = digests(cfg, MeshInfo(mesh, pod_axis=None), shape,
                                  jnp.float32, jnp.float32)
res['tp_sharded_ffn'] = digests(
    cfg, MeshInfo(mesh, pod_axis=None, fuse_tp=False), shape, jnp.float32,
    jnp.float32)
res['multi_pod_hier'] = digests(cfg8, MeshInfo(mesh2, pod_axis='pod'), shape,
                                jnp.float32, jnp.float32)
print('RESULT ' + json.dumps(res))
"""

CASES = ["bench_one_device", "single_pod_fused", "tp_sharded_ffn",
         "multi_pod_hier"]


def _run_current():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


# Row-wide scatters under the expert-pad scope, in the layer lowered at the
# widths of both MoE cells on one device: OLMoE's, and the DeepSeek share
# layer as its benchmark driver builds it.
ROW_SCATTER_SCRIPT = r"""
import dataclasses, json, math, re
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.compat import make_mesh
from repro.core.dispatch import MeshInfo, moe_dcra
from repro.models.moe import init_moe
from bench.drivers.moe_share_stack import _arch
from bench.ref.moe_share import init_params

SCATTER = re.compile(r'= \w+\[([\d,]*)\]\S* scatter\(.*'
                     r'update_window_dims=\{([\d,]*)\}.*op_name="([^"]*)"')


def expert_pad_row_scatters(cfg, info, params, x_shape):
    x = jax.ShapeDtypeStruct(x_shape, jnp.bfloat16)

    def moe_layer(p, x):
        with jax.default_matmul_precision('highest'):
            return moe_dcra(p, x, cfg, info)[0]

    text = jax.jit(moe_layer).lower(params, x).as_text(dialect='hlo',
                                                       debug_info=True)
    found = []
    for dims, window, name in SCATTER.findall(text):
        row = [int(d) for d in dims.split(',')][1:]
        if 'dcra.moe.expert_pad' in name and window and math.prod(row) > 1:
            found.append(dims + ' ' + name)
    return found


one = MeshInfo(make_mesh((1, 1, 1), ('data', 'expert', 'tp')), pod_axis=None)
res = {}
olmoe = get_config('olmoe-1b-7b')
olmoe = dataclasses.replace(olmoe, moe=dataclasses.replace(
    olmoe.moe, capacity_factor=2.0))
shapes = jax.eval_shape(lambda: init_moe(jax.random.key(0), olmoe))
params = {k: jax.ShapeDtypeStruct(
    v.shape, jnp.bfloat16 if k in ('wg', 'wu', 'wd') else jnp.float32)
    for k, v in shapes.items()}
res['olmoe-layer-fwd'] = expert_pad_row_scatters(olmoe, one, params,
                                                 (8, 512, 2048))

with open('bench/configs/deepseek-v3-moe-ep32.json') as f:
    ds = json.load(f)
first, held = ds['first_expert_held'], ds['n_routed_experts']
params = jax.eval_shape(lambda: init_params(
    jax.random.key(0), d_model=ds['hidden_size'],
    n_experts=ds['router_experts'], held=held,
    d_expert=ds['moe_intermediate_size'],
    d_shared=ds['n_shared_experts'] * ds['moe_intermediate_size'],
    bias_std=ds['e_score_correction_bias_std']))
share = dataclasses.replace(one, expert_share=(first, held))
res['deepseek-v3-moe-prefill'] = expert_pad_row_scatters(
    _arch(ds), share, params, (16, 4096, ds['hidden_size']))
print('RESULT ' + json.dumps(res))
"""


@pytest.fixture(scope="module")
def row_scatters():
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), root])
    out = subprocess.run([sys.executable, "-c", ROW_SCATTER_SCRIPT], env=env,
                         cwd=root, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("cell", ["olmoe-layer-fwd", "deepseek-v3-moe-prefill"])
def test_expert_buckets_return_rows_without_a_row_scatter(row_scatters, cell):
    """Rows leave the expert buckets by a gather through each received
    row's slot: no scatter under ``dcra.moe.expert_pad`` writes whole
    rows (the bucket's own scatters of slot ints are scalar)."""
    assert row_scatters[cell] == []


@pytest.fixture(scope="module")
def current():
    return _run_current()


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("case", CASES)
def test_olmoe_moe_dcra_lowers_to_the_same_hlo(current, golden, case):
    assert current[case] == golden[case]


if __name__ == "__main__":
    if "--regen" in sys.argv:
        res = _run_current()
        with open(GOLDEN, "w") as f:
            json.dump(res, f, indent=1)
        print(f"wrote {GOLDEN}")

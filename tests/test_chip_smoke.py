"""chip_smoke.py's phases, run in-process on the CPU at `.reduced()` widths
(Pallas in interpret mode), so a change that breaks the chip smoke run shows
up here first.  The TPU-only guard and the last-line contract live in
``main()`` and are checked on the CPU too: it must refuse to run."""
import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen3-0.6b").reduced()


def test_serve_phase_reduced(smoke, cfg):
    params = M.lm_init(jax.random.PRNGKey(0), cfg)
    out = smoke.serve_phase(cfg, params, slots=3, max_len=128, page_size=16,
                            chunk=16, decode_block=4,
                            prompt_lens=(32, 48, 16), gen=9,
                            decode_cfg="con4", seed=0)
    assert out["requests"] == 3 and out["kernels"] >= 1
    assert out["logit_max_abs_diff"] <= smoke.LOGIT_RTOL * out["logit_scale"]
    assert 0.0 <= out["token_agreement"] <= 1.0


def test_train_phase_reduced(smoke, cfg):
    out = smoke.train_phase(cfg, batch=2, seq=256, steps=2, remat="full",
                            attn_cfg="con2", seed=0)
    assert len(out["losses"]) == 2 and np.isfinite(out["losses"]).all()
    assert out["first_loss_diff"] <= smoke.LOSS_ATOL
    assert out["kernels"] >= 1


def test_main_refuses_without_a_tpu():
    """On a host whose JAX finds no TPU the script exits non-zero and
    prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not a TPU" in r.stderr


def test_mesh_phase_reduced_on_four_cpu_devices():
    """The --chips 4 phase on four virtual CPU devices: a 2x2 (data, model)
    step from make_mesh_for_host against the same step on one device."""
    code = (
        "import importlib.util, json\n"
        f"spec = importlib.util.spec_from_file_location('cs', "
        f"{os.path.join(ROOT, 'chip_smoke.py')!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        "from repro.configs import get_config\n"
        "cfg = get_config('qwen3-0.6b').reduced()\n"
        "print(json.dumps(cs.mesh_phase(cfg, batch=4, seq=64, remat='full',"
        " seed=0)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "loss_2x2" in r.stdout.splitlines()[-1]

"""CPU rehearsal of ``chip_smoke.py``: each phase at a tiny size, with the
Pallas kernels in interpret mode (the CPU backend's default).  The chip run
itself is ``python chip_smoke.py`` on a TPU host."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(n_layers=2, hidden=16, n_virtual=2, s_dim=8, h_in=1,
            precision="f32")


@pytest.fixture(scope="module")
def clog():
    return chip_smoke.CompileLog()


@pytest.fixture(scope="module")
def trained(clog):
    return chip_smoke.train_phase(n_particles=300, batch=2, steps=2,
                                  mode="interpret", widths=TINY, clog=clog)


def test_train_phase_rehearsal(trained):
    _, _, info = trained
    assert len(info["losses"]) == 2
    assert info["counts"]["edge_kernel"] > 0
    assert info["counts"].get("edge_layout_regroup", 0) == 0
    assert info["ref"]["max_rel"] <= chip_smoke.REF_TOL


def test_serve_phase_rehearsal(trained, clog):
    pipe, ref, _ = trained
    info = chip_smoke.serve_phase(pipe, ref, n_particles=300, steps=6,
                                  ref_frames=3, mode="interpret", clog=clog)
    assert [r["steps"] for r in info["requests"]] == [6, 6]
    assert info["requests"][1]["recompiles"] == 0
    assert info["program_builds"] == 1
    assert info["ref"]["max_rel"] <= chip_smoke.REF_TOL


def test_dispatch_check_refuses_jnp_fallback():
    report = {"mode": "interpret",
              "counts": {"edge_kernel": 4, "edge_jnp": 1,
                         "virtual_kernel": 4}}
    with pytest.raises(chip_smoke.SmokeFailure, match="edge pathway"):
        chip_smoke.check_dispatch(report, mode="interpret",
                                  layouts_from_data=True, phase="t",
                                  n_layers=4)
    report["counts"]["edge_jnp"] = 0
    with pytest.raises(chip_smoke.SmokeFailure, match="dispatch mode"):
        chip_smoke.check_dispatch(report, mode="tpu",
                                  layouts_from_data=True, phase="t",
                                  n_layers=4)
    with pytest.raises(chip_smoke.SmokeFailure, match="traced more than"):
        chip_smoke.check_dispatch(report, mode="interpret",
                                  layouts_from_data=True, phase="t",
                                  n_layers=2)


def test_dist_phase_rehearsal_four_host_devices():
    code = f"""
    import json
    import chip_smoke
    info = chip_smoke.dist_phase(n_dev=4, n_particles=600, steps=2,
                                 mode="interpret", widths={TINY!r},
                                 clog=chip_smoke.CompileLog())
    print("RESULT " + json.dumps({{"ref": info["ref"],
                                  "nodes": info["nodes"],
                                  "losses": info["losses"]}}))
    """
    env = dict(os.environ)
    env.update(XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    res = json.loads(line[-1][len("RESULT "):])
    assert len(res["nodes"]) == 4 and sum(res["nodes"]) == 600
    assert res["ref"]["max_rel"] <= chip_smoke.REF_TOL


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert not any(ln.startswith("{") for ln in out.splitlines())

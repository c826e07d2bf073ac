"""The examples/ user journey as a test: TFRecord write -> stf.data
pipeline -> MonitoredTrainingSession -> checkpoint resume -> SavedModel
export -> serve (mirrors the reference's tutorial workflow)."""

import os
import subprocess
import sys


def test_end_to_end_example_runs(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(repo, "examples", "train_mnist_end_to_end.py"),
         "--steps", "12", "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "DONE" in out.stdout
    assert "served predictions" in out.stdout


def test_data_parallel_example_runs():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable,
         os.path.join(repo, "examples", "train_bert_data_parallel.py"),
         "--dp", "8", "--steps", "3", "--recompute"],
        capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "spans 8 device(s)" in out.stdout, out.stdout[-500:]
    assert "replicated=True" in out.stdout


def test_text_qat_example_runs(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable,
         os.path.join(repo, "examples", "train_text_qat_pipeline.py"),
         "--steps", "80", "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=420, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "QAT training: loss" in out.stdout
    assert "end to end" in out.stdout

"""The examples/ scripts must stay runnable (reference model: the
example/ tree is part of the user-facing surface; CI runs smoke
configs)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + args, cwd=ROOT,
                          capture_output=True, text=True,
                          timeout=timeout, env=env)


def test_example_mnist_mlp_runs():
    r = _run(["examples/train_mnist_mlp.py", "--epochs", "2",
              "--synthetic", "--ctx", "cpu(0)"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "running on cpu(0): cpu" in r.stdout
    assert "epoch 1:" in r.stdout


def test_example_names_the_chip_and_fails_without_it():
    """The default context is the host, so an example meant for the chip
    names tpu(0) — and on a host without one it fails at the door instead
    of quietly training on CPU cores."""
    r = _run(["examples/train_mnist_mlp.py", "--epochs", "1",
              "--synthetic"])
    assert r.returncode != 0
    assert "context tpu(0)" in r.stderr and "epoch 0:" not in r.stdout


def test_example_recommender_runs():
    r = _run(["examples/train_recommender.py", "--steps", "30",
              "--vocab", "5000", "--batch-size", "128"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "sparse grads:" in r.stdout
    assert "sparse.grad_rows:" in r.stdout


def test_example_serve_continuous_batching_runs():
    r = _run(["examples/serve_continuous_batching.py", "--clients", "2",
              "--requests", "20", "--ctx", "cpu(0)"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "served 40 requests" in r.stdout
    assert "batch efficiency" in r.stdout


def test_example_serve_generation_runs():
    r = _run(["examples/serve_generation.py", "--clients", "2",
              "--requests", "6", "--ctx", "cpu(0)"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "completed 12 generations" in r.stdout
    assert "KV blocks used after drain: 0" in r.stdout


def test_example_serve_http_runs():
    r = _run(["examples/serve_http.py", "--clients", "2",
              "--requests", "4", "--generations", "2", "--ctx", "cpu(0)"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "bitwise: OK" in r.stdout
    assert "zero dropped: OK" in r.stdout
    assert "low-priority predict -> 429" in r.stdout
    assert "KV blocks used: 0" in r.stdout


def test_example_elastic_fleet_runs():
    """3-worker fleet, one host SIGKILLed mid-run: the example must
    print both survivors' re-form lines and the OK marker."""
    r = _run(["examples/elastic_fleet.py", "--target", "8",
              "--kill-step", "3"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "ELASTIC_EXAMPLE_OK" in r.stdout
    assert "killed as planned" in r.stdout
    assert r.stdout.count("fleet re-formed at generation 1") == 2


def test_example_selftune_controllers_runs():
    r = _run(["examples/selftune_controllers.py", "--steps", "4",
              "--ops", "120", "--cpu"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SELFTUNE_EXAMPLE_OK" in r.stdout
    assert "bulk_size:" in r.stdout      # at least one live decision


def test_example_imagenet_style_runs(tmp_path):
    rec = str(tmp_path / "t.rec")
    r = _run(["examples/train_imagenet_style.py", "--epochs", "1",
              "--batch-size", "8", "--image-size", "64",
              "--rec", rec])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "exported" in r.stdout


def test_example_char_lm_bucketing_runs():
    r = _run(["examples/train_char_lm_bucketing.py", "--epochs", "4",
              "--cpu"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "final perplexity" in r.stdout


def test_example_translate_nmt_runs():
    r = _run(["examples/translate_nmt.py", "--epochs", "200", "--cpu"],
             timeout=1200)
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-1500:])
    assert "translation OK" in r.stdout


def test_example_bert_pretrain_runs():
    r = _run(["examples/pretrain_bert_mlm.py", "--steps", "6",
              "--batch", "2", "--seq", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if "loss" in l]
    first = float(lines[0].split()[-1])
    last = float(lines[-1].split()[-1])
    assert last < first, (first, last)


def test_example_longformer_longctx_runs():
    r = _run(["examples/train_longformer_longctx.py", "--steps", "6",
              "--seq", "256"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout

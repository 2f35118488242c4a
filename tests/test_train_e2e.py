"""End-to-end driver tests on CPU: loss goes down, checkpoint/resume is
exact, serve driver generates."""
import json

import numpy as np
import pytest

from repro.launch import train as T
from repro.launch import serve as S

pytestmark = pytest.mark.slow  # full train/serve loops: non-blocking CI job


def test_train_loss_decreases(tmp_path):
    # small reduced dense arch, enough steps to see learning
    hist = T.main([
        "--arch", "granite-20b", "--reduced", "--steps", "30",
        "--batch", "8", "--seq", "64", "--lr", "3e-3", "--warmup", "5",
        "--log-every", "50",
        "--metrics-out", str(tmp_path / "m.json"),
    ])
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert np.isfinite(last)
    assert last < first - 0.3, (first, last)
    assert (tmp_path / "m.json").exists()


def test_train_resume_is_seamless(tmp_path):
    common = ["--arch", "llama3-8b", "--reduced", "--batch", "4",
              "--seq", "32", "--save-every", "5",
              "--ckpt-dir", str(tmp_path / "ck")]
    T.main(common + ["--steps", "5"])
    hist2 = T.main(common + ["--steps", "8"])
    # resumed exactly at step 5
    assert hist2[0]["step"] == 5
    assert len(hist2) == 3


def test_train_with_accumulation_matches_plain():
    h1 = T.main(["--arch", "minitron-4b", "--reduced", "--steps", "3",
                 "--batch", "8", "--seq", "32", "--accum", "1",
                 "--lr", "0"])
    h2 = T.main(["--arch", "minitron-4b", "--reduced", "--steps", "3",
                 "--batch", "8", "--seq", "32", "--accum", "4",
                 "--lr", "0"])
    # with lr=0 params never change; losses must agree exactly per step
    for a, b in zip(h1, h2):
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)


def test_serve_generates():
    report = S.main(["--arch", "qwen2.5-32b", "--reduced", "--requests", "2",
                     "--slots", "2", "--prompt-len", "8", "--gen", "4"])
    assert sorted(report.completed) == ["req0", "req1"]
    assert all(len(report.outputs[r]) == 4 for r in report.completed)

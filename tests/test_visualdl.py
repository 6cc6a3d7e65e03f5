"""VisualDL logging tier (§5.5: LogWriter + hapi VisualDL callback)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.visualdl import LogWriter, VisualDL


class TestLogWriter:
    @pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
    def test_scalar_events_written(self, tmp_path):
        d = str(tmp_path / "log")
        with LogWriter(d) as w:
            for i in range(5):
                w.add_scalar("loss", 1.0 / (i + 1), step=i)
            w.add_text("config", "lr=0.1", step=0)
            w.add_histogram("weights", np.random.randn(100), step=0)
        files = os.listdir(d)
        assert files, "no event files written"
        # either TB event files or the JSONL fallback
        assert any(f.startswith("events") or f.endswith(".jsonl")
                   for f in files)

    def test_jsonl_fallback_readable(self, tmp_path, monkeypatch):
        import paddle_tpu.visualdl as vdl
        # force the fallback by making the TB import fail
        import builtins
        real_import = builtins.__import__

        def fake(name, *a, **k):
            if name.startswith("torch"):
                raise ImportError("no torch")
            return real_import(name, *a, **k)
        monkeypatch.setattr(builtins, "__import__", fake)
        d = str(tmp_path / "log")
        w = vdl.LogWriter(d)
        w.add_scalar("x", 2.5, step=1)
        w.close()
        monkeypatch.setattr(builtins, "__import__", real_import)
        rows = [json.loads(l) for l in
                open(os.path.join(d, "scalars.jsonl"))]
        assert rows[0]["tag"] == "x" and rows[0]["value"] == 2.5

    def _jsonl_writer(self, tmp_path, monkeypatch):
        import builtins

        import paddle_tpu.visualdl as vdl
        real_import = builtins.__import__

        def fake(name, *a, **k):
            if name.startswith("torch"):
                raise ImportError("no torch")
            return real_import(name, *a, **k)
        monkeypatch.setattr(builtins, "__import__", fake)
        w = vdl.LogWriter(str(tmp_path / "log"))
        monkeypatch.setattr(builtins, "__import__", real_import)
        return w

    def test_flush_flushes_jsonl_backend(self, tmp_path, monkeypatch):
        w = self._jsonl_writer(tmp_path, monkeypatch)
        w.add_scalar("y", 1.0, step=0)
        w.flush()                             # must reach the jsonl too
        path = os.path.join(w.logdir, "scalars.jsonl")
        assert json.loads(open(path).readline())["tag"] == "y"
        w.close()

    def test_close_idempotent(self, tmp_path, monkeypatch):
        w = self._jsonl_writer(tmp_path, monkeypatch)
        w.add_scalar("z", 1.0)
        w.close()
        w.close()                             # second close must not raise
        with LogWriter(str(tmp_path / "log2")) as w2:
            w2.add_scalar("a", 1.0)
            w2.close()                        # explicit close + __exit__

    def test_add_text_records_time(self, tmp_path, monkeypatch):
        import time
        w = self._jsonl_writer(tmp_path, monkeypatch)
        before = time.time()
        w.add_text("config", "lr=0.1", step=2)
        w.close()
        (row,) = [json.loads(l) for l in
                  open(os.path.join(w.logdir, "scalars.jsonl"))]
        # parity with add_scalar: text records carry a wall-clock stamp
        assert row["tag"] == "config" and row["text"] == "lr=0.1"
        assert before <= row["time"] <= time.time()


class TestVisualDLCallback:
    def test_fit_logs_metrics(self, tmp_path):
        from paddle_tpu.io import DataLoader, TensorDataset
        paddle.seed(0)
        x = np.random.default_rng(0).standard_normal(
            (32, 4)).astype(np.float32)
        y = (x.sum(1) > 0).astype(np.int64)
        ds = TensorDataset([paddle.to_tensor(x), paddle.to_tensor(y)])
        model = paddle.Model(nn.Sequential(nn.Linear(4, 2)))
        model.prepare(paddle.optimizer.Adam(
            learning_rate=0.1, parameters=model.network.parameters()),
            nn.CrossEntropyLoss(), paddle.metric.Accuracy())
        d = str(tmp_path / "vdl")
        cb = VisualDL(d)
        model.fit(DataLoader(ds, batch_size=8), epochs=2, callbacks=[cb],
                  verbose=0)
        assert cb._step == 8            # 4 batches x 2 epochs
        assert os.listdir(d)

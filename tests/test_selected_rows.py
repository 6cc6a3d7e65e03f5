"""SelectedRows row-sparse gradients (framework/selected_rows.h +
selected_rows_functor MergeAdd + sgd_op/adam_op SelectedRows branches),
emitted by Embedding(sparse=True) on the eager tape."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.selected_rows import SelectedRows

RNG = np.random.default_rng(0)


class TestSelectedRowsType:
    def test_merge_accumulates_duplicates(self):
        sr = SelectedRows([1, 3, 1], np.array([[1.0], [2.0], [10.0]]), 5)
        m = sr.merge()
        d = {int(r): float(v) for r, v in zip(m.rows, m.values[:, 0])}
        assert d == {1: 11.0, 3: 2.0}
        np.testing.assert_allclose(
            np.asarray(m.to_dense())[:, 0], [0, 11, 0, 2, 0])

    def test_add_sparse_sparse_and_dense(self):
        a = SelectedRows([0], np.array([[1.0, 1.0]]), 3)
        b = SelectedRows([2], np.array([[2.0, 2.0]]), 3)
        c = (a + b).merge()
        np.testing.assert_allclose(np.asarray(c.to_dense()),
                                   [[1, 1], [0, 0], [2, 2]])
        dense = np.ones((3, 2), np.float32)
        out = a + dense
        np.testing.assert_allclose(np.asarray(out),
                                   [[2, 2], [1, 1], [1, 1]])

    def test_scalar_mul(self):
        a = SelectedRows([1], np.array([[2.0]]), 2)
        np.testing.assert_allclose(
            np.asarray((a * 3).to_dense()), [[0.0], [6.0]])


class TestSparseEmbeddingGrad:
    def test_grad_is_selected_rows_and_matches_dense(self):
        vocab, dim = 50, 4
        w = RNG.standard_normal((vocab, dim)).astype(np.float32)
        ids = np.array([[1, 2, 2], [7, 1, 49]], np.int64)

        sp = paddle.create_parameter([vocab, dim], "float32")
        sp.set_value(w)
        out = F.embedding(paddle.to_tensor(ids), sp, sparse=True)
        (out * 2).sum().backward()
        assert isinstance(sp._grad, SelectedRows)
        assert sp._grad.rows.shape[0] == ids.size  # pre-merge, per lookup

        dn = paddle.create_parameter([vocab, dim], "float32")
        dn.set_value(w)
        out2 = F.embedding(paddle.to_tensor(ids), dn, sparse=False)
        (out2 * 2).sum().backward()
        np.testing.assert_allclose(sp._grad.numpy(), dn.grad.numpy(),
                                   rtol=1e-6)

    def test_padding_idx_rows_zeroed(self):
        sp = paddle.create_parameter([10, 2], "float32")
        ids = np.array([[0, 3]], np.int64)
        out = F.embedding(paddle.to_tensor(ids), sp, padding_idx=0,
                          sparse=True)
        out.sum().backward()
        g = sp._grad.numpy()
        np.testing.assert_allclose(g[0], 0.0)
        np.testing.assert_allclose(g[3], 1.0)

    def test_two_backwards_accumulate(self):
        sp = paddle.create_parameter([8, 2], "float32")
        for _ in range(2):
            out = F.embedding(paddle.to_tensor(np.array([[1]])), sp,
                              sparse=True)
            out.sum().backward()
        assert isinstance(sp._grad, SelectedRows)
        np.testing.assert_allclose(sp._grad.numpy()[1], [2.0, 2.0])

    def test_mixed_dense_sparse_densifies(self):
        sp = paddle.create_parameter([8, 2], "float32")
        out = F.embedding(paddle.to_tensor(np.array([[1]])), sp,
                          sparse=True)
        loss = out.sum() + (sp * 0.5).sum()
        loss.backward()
        g = sp.grad
        # dense contribution everywhere + sparse row bump
        arr = g.numpy() if hasattr(g, "numpy") else np.asarray(g)
        np.testing.assert_allclose(arr[0], [0.5, 0.5])
        np.testing.assert_allclose(arr[1], [1.5, 1.5])


class TestSparseOptimizerSteps:
    def _pair(self, vocab=20, dim=3, opt_cls=None, **kw):
        w = RNG.standard_normal((vocab, dim)).astype(np.float32)
        params = []
        for sparse in (True, False):
            p = paddle.create_parameter([vocab, dim], "float32")
            p.set_value(w)
            params.append(p)
        return params

    def test_sgd_sparse_matches_dense(self):
        sp, dn = self._pair()
        ids = np.array([[3, 5, 3]], np.int64)
        for p, sparse in ((sp, True), (dn, False)):
            opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[p])
            out = F.embedding(paddle.to_tensor(ids), p, sparse=sparse)
            (out ** 2).sum().backward()
            opt.step()
        np.testing.assert_allclose(sp.numpy(), dn.numpy(), rtol=1e-6)

    def test_adam_lazy_touches_only_rows(self):
        sp, dn = self._pair()
        ids = np.array([[3, 5]], np.int64)
        before = sp.numpy().copy()
        opt = paddle.optimizer.Adam(learning_rate=0.1, parameters=[sp],
                                    lazy_mode=True)
        out = F.embedding(paddle.to_tensor(ids), sp, sparse=True)
        out.sum().backward()
        opt.step()
        after = sp.numpy()
        changed = np.abs(after - before).sum(axis=1) > 0
        assert changed[3] and changed[5] and changed.sum() == 2

    def test_adam_nonlazy_sparse_matches_dense(self):
        sp, dn = self._pair()
        ids = np.array([[3, 5, 3]], np.int64)
        for p, sparse in ((sp, True), (dn, False)):
            opt = paddle.optimizer.Adam(learning_rate=0.05, parameters=[p])
            for _ in range(3):
                out = F.embedding(paddle.to_tensor(ids), p, sparse=sparse)
                (out ** 2).sum().backward()
                opt.step()
                opt.clear_grad()
        np.testing.assert_allclose(sp.numpy(), dn.numpy(), rtol=1e-5)

    @pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
    def test_sparse_embedding_model_trains(self):
        paddle.seed(0)
        emb = nn.Embedding(100, 8, sparse=True)
        head = nn.Linear(8, 2)
        opt = paddle.optimizer.Adam(
            learning_rate=0.05, lazy_mode=True,
            parameters=emb.parameters() + head.parameters())
        rng = np.random.default_rng(1)
        losses = []
        for _ in range(30):
            ids = rng.integers(0, 100, size=(16, 5))
            y = (ids.sum(1) % 2).astype(np.int64)
            pooled = emb(paddle.to_tensor(ids)).mean(axis=1)
            loss = F.cross_entropy(head(pooled), paddle.to_tensor(y))
            loss.backward()
            assert isinstance(emb.weight._grad, SelectedRows)
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses

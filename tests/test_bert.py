"""BERT/ERNIE family: forward shapes, masking semantics, MLM loss, fleet DP
training step (driver config #3 pattern), tp sharding via the engine."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.text.models import (
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
    bert_tiny,
)


@pytest.fixture
def config():
    return bert_tiny(use_flash_attention=False)


class TestBertForward:
    def test_shapes(self, config):
        paddle.seed(0)
        model = BertModel(config)
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, config.vocab_size, (2, 16))
            .astype("int64"))
        seq, pooled = model(ids)
        assert tuple(seq.shape) == (2, 16, config.hidden_size)
        assert tuple(pooled.shape) == (2, config.hidden_size)

    def test_attention_mask_blocks_padding(self, config):
        """Changing a masked-out position must not change unmasked outputs."""
        paddle.seed(1)
        model = BertModel(config)
        model.eval()
        rng = np.random.RandomState(1)
        ids = rng.randint(0, config.vocab_size, (1, 8)).astype("int64")
        mask = np.ones((1, 8), "float32")
        mask[0, 6:] = 0.0  # last two positions are padding
        seq1, _ = model(paddle.to_tensor(ids), None, paddle.to_tensor(mask))
        ids2 = ids.copy()
        ids2[0, 6:] = (ids2[0, 6:] + 17) % config.vocab_size
        seq2, _ = model(paddle.to_tensor(ids2), None, paddle.to_tensor(mask))
        np.testing.assert_allclose(seq1.numpy()[0, :6], seq2.numpy()[0, :6],
                                   atol=1e-5)

    @pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                           ("bfloat16", 0.02)])
    def test_xla_tier_matches_the_plain_softmax_path(self, monkeypatch,
                                                     dtype, tol):
        """With an explicit mask the XLA tier ([b, l, h, d] into the
        chunk body, bias inside the chunk, divide after PV) gives the
        logits of the path it replaced: [b, h, l, d] transposes around
        one plain softmax over the whole biased square."""
        import jax.numpy as jnp

        from paddle_tpu.ops import attention as att
        from paddle_tpu.text.models import bert as bert_mod

        paddle.seed(5)
        model = BertForPretraining(bert_tiny(use_flash_attention=True))
        model.eval()
        if dtype == "bfloat16":
            model.bfloat16()
        rng = np.random.RandomState(5)
        ids = paddle.to_tensor(rng.randint(0, 1024, (2, 128)).astype("int64"))
        mask = np.ones((2, 128), "float32")
        mask[0, 100:] = 0.0
        mask[1, 77:] = 0.0
        mask = paddle.to_tensor(mask)

        def plain(q, k, v, causal=False, bias=None, use_flash=True,
                  layout="bhld"):
            assert layout == "blhd" and not causal
            tr = lambda t: t.transpose(0, 2, 1, 3)
            s = jnp.einsum("bhqd,bhkd->bhqk", tr(q), tr(k),
                           preferred_element_type=jnp.float32)
            s = s * q.shape[-1] ** -0.5 + bias
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return tr(jnp.einsum("bhqk,bhkd->bhqd", p, tr(v)))

        att.set_attention_impl("xla")
        try:
            logits, nsp = model(ids, None, mask)
        finally:
            att.set_attention_impl("auto")
        monkeypatch.setattr(bert_mod, "dot_product_attention", plain)
        ref_logits, ref_nsp = model(ids, None, mask)
        f32 = lambda t: np.asarray(t.numpy(), np.float32)
        assert np.isfinite(f32(logits)).all()
        np.testing.assert_allclose(f32(logits), f32(ref_logits),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(f32(nsp), f32(ref_nsp), rtol=tol, atol=tol)

    def test_token_type_changes_output(self, config):
        paddle.seed(2)
        model = BertModel(config)
        model.eval()
        ids = paddle.to_tensor(
            np.random.RandomState(2).randint(0, config.vocab_size, (1, 8))
            .astype("int64"))
        tt0 = paddle.to_tensor(np.zeros((1, 8), "int64"))
        tt1 = paddle.to_tensor(np.ones((1, 8), "int64"))
        s0, _ = model(ids, tt0)
        s1, _ = model(ids, tt1)
        assert np.abs(s0.numpy() - s1.numpy()).max() > 1e-4


class TestBertPretraining:
    def test_mlm_loss_ignores_unmasked(self, config):
        paddle.seed(3)
        model = BertForPretraining(config)
        model.eval()
        rng = np.random.RandomState(3)
        ids = paddle.to_tensor(
            rng.randint(0, config.vocab_size, (2, 12)).astype("int64"))
        out = model(ids)
        labels_none = paddle.to_tensor(np.full((2, 12), -100, "int64"))
        loss0 = model.loss_fn(out, labels_none)
        assert float(loss0.numpy()) == 0.0
        labels = np.full((2, 12), -100, "int64")
        labels[0, 3] = 7
        loss1 = model.loss_fn(out, paddle.to_tensor(labels))
        assert float(loss1.numpy()) > 0.0

    def test_training_reduces_mlm_loss(self, config):
        paddle.seed(4)
        model = BertForPretraining(config)
        opt = paddle.optimizer.Adam(learning_rate=5e-4,
                                    parameters=model.parameters())
        rng = np.random.RandomState(4)
        ids = rng.randint(0, config.vocab_size, (4, 16)).astype("int64")
        labels = np.full((4, 16), -100, "int64")
        labels[:, ::4] = ids[:, ::4]  # predict every 4th token
        tid = paddle.to_tensor(ids)
        tlab = paddle.to_tensor(labels)
        losses = []
        for _ in range(20):
            loss = model.loss_fn(model(tid), tlab)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < 0.75 * losses[0]


class TestBertFleet:
    def test_dp_mp_engine_step(self, config):
        """BERT-base pattern (config #3): fleet engine over dp×mp mesh."""
        import numpy as onp
        from jax.sharding import Mesh

        from paddle_tpu.distributed.fleet.engine import ParallelTrainStep

        paddle.seed(5)
        model = BertForSequenceClassification(config, num_classes=3)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        devs = onp.array(jax.devices()[:4]).reshape(2, 2)
        mesh = Mesh(devs, ("dp", "mp"))

        def loss_fn(logits, labels):
            from paddle_tpu.nn import functional as F

            return F.cross_entropy(logits, labels)

        step = ParallelTrainStep(model, loss_fn, opt, mesh,
                                 compute_dtype=None)
        rng = onp.random.RandomState(5)
        ids = rng.randint(0, config.vocab_size, (8, 12)).astype("int64")
        y = rng.randint(0, 3, (8, 1)).astype("int64")
        losses = [float(step((ids,), (y,)).numpy()) for _ in range(8)]
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

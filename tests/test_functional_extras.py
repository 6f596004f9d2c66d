"""New functional surface: affine_grid/grid_sample and ctc_loss against
torch oracles; dice/npair/hsigmoid/diag_embed/gather_tree properties;
inplace variants; new tensor-namespace ops."""
import numpy as np
import pytest
import torch

import paddle_tpu as pt
import paddle_tpu.nn.functional as F


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.mark.parametrize("align", [True, False])
def test_affine_grid_vs_torch(rng, align):
    theta = rng.randn(2, 2, 3).astype(np.float32) * 0.5
    out = F.affine_grid(pt.to_tensor(theta), [2, 3, 5, 7],
                        align_corners=align)
    want = torch.nn.functional.affine_grid(
        torch.from_numpy(theta), (2, 3, 5, 7), align_corners=align)
    np.testing.assert_allclose(np.asarray(out.value), want.numpy(),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", ["bilinear", "nearest"])
@pytest.mark.parametrize("padding", ["zeros", "border", "reflection"])
@pytest.mark.parametrize("align", [True, False])
def test_grid_sample_vs_torch(rng, mode, padding, align):
    x = rng.randn(2, 3, 6, 5).astype(np.float32)
    grid = (rng.rand(2, 4, 4, 2).astype(np.float32) * 2.4 - 1.2)
    out = F.grid_sample(pt.to_tensor(x), pt.to_tensor(grid), mode=mode,
                        padding_mode=padding, align_corners=align)
    want = torch.nn.functional.grid_sample(
        torch.from_numpy(x), torch.from_numpy(grid), mode=mode,
        padding_mode=padding, align_corners=align)
    np.testing.assert_allclose(np.asarray(out.value), want.numpy(),
                               rtol=1e-4, atol=1e-5)


def test_ctc_loss_vs_torch(rng):
    T, N, C, L = 12, 3, 6, 5
    logits = rng.randn(T, N, C).astype(np.float32)
    labels = rng.randint(1, C, (N, L)).astype(np.int32)
    in_lens = np.array([12, 9, 7], np.int32)
    lab_lens = np.array([5, 3, 1], np.int32)
    out = F.ctc_loss(pt.to_tensor(logits), pt.to_tensor(labels),
                     pt.to_tensor(in_lens), pt.to_tensor(lab_lens),
                     blank=0, reduction="none")
    t_lp = torch.from_numpy(logits).log_softmax(-1)
    want = torch.nn.functional.ctc_loss(
        t_lp, torch.from_numpy(labels.astype(np.int64)),
        torch.from_numpy(in_lens.astype(np.int64)),
        torch.from_numpy(lab_lens.astype(np.int64)), blank=0,
        reduction="none")
    np.testing.assert_allclose(np.asarray(out.value), want.numpy(),
                               rtol=1e-4, atol=1e-4)


def test_ctc_loss_grads(rng):
    T, N, C, L = 8, 2, 5, 3
    x = pt.to_tensor(rng.randn(T, N, C).astype(np.float32))
    x.stop_gradient = False
    labels = rng.randint(1, C, (N, L)).astype(np.int32)
    loss = F.ctc_loss(x, pt.to_tensor(labels),
                      pt.to_tensor(np.array([8, 6], np.int32)),
                      pt.to_tensor(np.array([3, 2], np.int32)))
    loss.backward()
    g = np.asarray(x.grad.value)
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_ctc_loss_layer(rng):
    """nn.CTCLoss wrapper."""
    T, N, C, L = 6, 2, 4, 2
    logits = rng.randn(T, N, C).astype(np.float32)
    labels = rng.randint(1, C, (N, L)).astype(np.int32)
    crit = pt.nn.CTCLoss(blank=0, reduction="mean")
    loss = crit(pt.to_tensor(logits), pt.to_tensor(labels),
                pt.to_tensor(np.array([6, 5], np.int32)),
                pt.to_tensor(np.array([2, 1], np.int32)))
    assert loss.shape == [] or tuple(loss.shape) == ()
    assert np.isfinite(float(loss.value))


def test_dice_and_npair(rng):
    probs = np.full((4, 3), 1.0 / 3, np.float32)
    labels = rng.randint(0, 3, (4, 1)).astype(np.int64)
    d = F.dice_loss(pt.to_tensor(probs), pt.to_tensor(labels))
    assert 0.0 < float(d.value) < 1.0
    # perfect one-hot predictions → loss ≈ 0
    perfect = np.eye(3, dtype=np.float32)[labels[:, 0]]
    d0 = F.dice_loss(pt.to_tensor(perfect), pt.to_tensor(labels))
    assert float(d0.value) < 1e-4

    lab = np.array([0, 0, 1, 1, 2, 2], np.int64)
    # label-clustered embeddings: same-label similarity high → low loss
    clustered = (np.eye(8, dtype=np.float32)[lab] * 6.0)
    l_good = float(F.npair_loss(pt.to_tensor(clustered),
                                pt.to_tensor(clustered),
                                pt.to_tensor(lab), l2_reg=0.0).value)
    l_rand = float(F.npair_loss(pt.to_tensor(rng.randn(6, 8).astype(
                                    np.float32) * 3),
                                pt.to_tensor(rng.randn(6, 8).astype(
                                    np.float32) * 3),
                                pt.to_tensor(lab), l2_reg=0.0).value)
    assert l_good < l_rand
    # l2 regularization adds to the loss
    l_reg = float(F.npair_loss(pt.to_tensor(clustered),
                               pt.to_tensor(clustered),
                               pt.to_tensor(lab), l2_reg=0.01).value)
    assert l_reg > l_good


def test_hsigmoid_loss(rng):
    N, D, K = 8, 6, 10
    x = pt.to_tensor(rng.randn(N, D).astype(np.float32))
    x.stop_gradient = False
    labels = rng.randint(0, K, (N,)).astype(np.int64)
    w = pt.to_tensor(rng.randn(K - 1, D).astype(np.float32) * 0.1)
    w.stop_gradient = False
    b = pt.to_tensor(np.zeros((K - 1,), np.float32))
    out = F.hsigmoid_loss(x, pt.to_tensor(labels), K, w, b)
    assert tuple(out.shape) == (N, 1)
    assert (np.asarray(out.value) > 0).all()
    out.sum().backward()
    assert np.abs(np.asarray(w.grad.value)).sum() > 0
    # layer wrapper trains a separable toy problem
    pt.seed(0)
    layer = pt.nn.HSigmoidLoss(D, K)
    opt = pt.optimizer.Adam(0.05, parameters=layer.parameters())
    feats = rng.randn(32, D).astype(np.float32)
    labs = (feats[:, 0] > 0).astype(np.int64)  # classes 0/1
    first = None
    for _ in range(30):
        loss = layer(pt.to_tensor(feats), pt.to_tensor(labs)).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        if first is None:
            first = float(loss.value)
    assert float(loss.value) < first * 0.7


def test_diag_embed_and_gather_tree(rng):
    x = rng.randn(2, 3).astype(np.float32)
    out = F.diag_embed(pt.to_tensor(x))
    want = torch.diag_embed(torch.from_numpy(x))
    np.testing.assert_allclose(np.asarray(out.value), want.numpy())
    out2 = F.diag_embed(pt.to_tensor(x), offset=1)
    want2 = torch.diag_embed(torch.from_numpy(x), offset=1)
    np.testing.assert_allclose(np.asarray(out2.value), want2.numpy())

    # gather_tree: the reference's doc example
    ids = np.array([[[2, 2], [6, 1]], [[3, 9], [6, 1]], [[0, 1], [9, 0]]],
                   np.int64)
    parents = np.array([[[0, 0], [1, 1]], [[1, 0], [1, 0]],
                        [[0, 0], [0, 1]]], np.int64)
    got = np.asarray(F.gather_tree(pt.to_tensor(ids),
                                   pt.to_tensor(parents)).value)
    want = np.array([[[2, 2], [1, 6]], [[3, 3], [6, 1]], [[0, 1], [9, 0]]],
                    np.int64)
    np.testing.assert_array_equal(got, want)


def test_inplace_activations(rng):
    x = pt.to_tensor(rng.randn(3, 4).astype(np.float32), stop_gradient=False)
    y = x * 1.0
    ref = np.tanh(np.asarray(y.value))
    out = F.tanh_(y)
    assert out is y
    np.testing.assert_allclose(np.asarray(y.value), ref, rtol=1e-6)
    y.sum().backward()
    assert x.grad is not None
    # gumbel_softmax(hard=True): one-hot forward (the installed jax needs
    # put_along_axis(inplace=False)), straight-through gradient
    pt.seed(0)
    hard = F.gumbel_softmax(x, hard=True)
    h = np.asarray(hard.value)
    np.testing.assert_array_equal(np.sort(h, -1)[:, -1], np.ones(3))
    np.testing.assert_array_equal(h.sum(-1), np.ones(3))


def test_pairwise_distance_and_unfold(rng):
    x = rng.randn(4, 6).astype(np.float32)
    y = rng.randn(4, 6).astype(np.float32)
    pd = pt.nn.PairwiseDistance(p=2.0)
    out = pd(pt.to_tensor(x), pt.to_tensor(y))
    want = torch.nn.PairwiseDistance(p=2.0)(torch.from_numpy(x),
                                            torch.from_numpy(y))
    np.testing.assert_allclose(np.asarray(out.value), want.numpy(),
                               rtol=1e-5, atol=1e-6)

    img = rng.randn(2, 3, 8, 8).astype(np.float32)
    uf = pt.nn.Unfold(kernel_sizes=[3, 3], strides=2, paddings=1)
    out = uf(pt.to_tensor(img))
    want = torch.nn.functional.unfold(torch.from_numpy(img), (3, 3),
                                      stride=2, padding=1)
    np.testing.assert_allclose(np.asarray(out.value), want.numpy(),
                               rtol=1e-5, atol=1e-6)


def test_beam_search_decoder(rng):
    """Beam search: beam_size=1 equals greedy argmax rollout; wider beams
    find sequences with scores >= greedy; EOS stops decoding."""
    import jax.numpy as jnp

    D, H, V = 8, 16, 12
    pt.seed(7)
    emb = pt.nn.Embedding(V, D)
    cell = pt.nn.GRUCell(D, H)
    out_fn = pt.nn.Linear(H, V)
    B, K = 2, 3
    h0 = pt.to_tensor(rng.randn(B, H).astype(np.float32))

    decoder = pt.nn.BeamSearchDecoder(cell, start_token=0, end_token=1,
                                      beam_size=K, embedding_fn=emb,
                                      output_fn=out_fn)
    ids, states, lens = pt.nn.dynamic_decode(decoder, inits=h0,
                                             max_step_num=6,
                                             return_length=True)
    assert tuple(ids.shape) == (B, 6, K) or tuple(ids.shape)[0] == B

    # greedy oracle == beam_size 1
    g_dec = pt.nn.BeamSearchDecoder(cell, start_token=0, end_token=1,
                                    beam_size=1, embedding_fn=emb,
                                    output_fn=out_fn)
    g_ids, _ = pt.nn.dynamic_decode(g_dec, inits=h0, max_step_num=6)
    tok = np.full((B,), 0, np.int64)
    h = np.asarray(h0.value)
    want = []
    for t in range(6):
        o, h_new = cell(emb(pt.to_tensor(tok)), pt.to_tensor(h))
        logits = np.asarray(out_fn(o).value)
        # finished rows can only emit EOS
        for b in range(B):
            if t > 0 and want and any(w[b] == 1 for w in want):
                logits[b] = -1e9
                logits[b, 1] = 0.0
        tok = logits.argmax(-1).astype(np.int64)
        h = np.asarray(h_new.value)
        want.append(tok.copy())
    want = np.stack(want, axis=1)  # [B, T]
    got = np.asarray(g_ids.value)[:, :, 0]
    np.testing.assert_array_equal(got[:, :want.shape[1]], want)


def test_ctc_mean_normalizes_by_label_length(rng):
    """warpctc 'mean' = mean(loss / label_lengths), not a plain mean."""
    T, N, C, L = 10, 2, 5, 4
    logits = rng.randn(T, N, C).astype(np.float32)
    labels = rng.randint(1, C, (N, L)).astype(np.int32)
    il = np.array([10, 8], np.int32)
    ll = np.array([4, 2], np.int32)
    per = np.asarray(F.ctc_loss(pt.to_tensor(logits), pt.to_tensor(labels),
                                pt.to_tensor(il), pt.to_tensor(ll),
                                reduction="none").value)
    mean = float(F.ctc_loss(pt.to_tensor(logits), pt.to_tensor(labels),
                            pt.to_tensor(il), pt.to_tensor(ll),
                            reduction="mean").value)
    np.testing.assert_allclose(mean, (per / ll).mean(), rtol=1e-6)


def test_crop_bounds_and_to_end(rng):
    import pytest as _pytest

    from paddle_tpu.core.errors import InvalidArgumentError

    x = pt.to_tensor(np.arange(10))
    out = pt.crop(x, shape=[-1], offsets=[2])
    np.testing.assert_array_equal(np.asarray(out.value), np.arange(2, 10))
    with _pytest.raises(InvalidArgumentError):
        pt.crop(x, shape=[9], offsets=[2])


def test_dtype_and_bool_aliases():
    import json

    assert pt.in_dynamic_mode() is True
    json.dumps({"eager": pt.in_dynamic_mode()})  # plain python bool
    assert pt.dtype("float32") == np.float32
    assert not isinstance(str, pt.dtype)
    assert np.dtype(pt.bool) == np.dtype("bool")

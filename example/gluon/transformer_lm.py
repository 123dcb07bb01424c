"""Causal transformer language model (the sequence-ops configuration).

A GPT-style decoder built from gluon blocks whose attention runs through
the framework's fused kernel (``_contrib_flash_attention`` — the Pallas
tiled online-softmax kernel on TPU, XLA reference elsewhere).  Trains
char-level copy/pattern data and reports next-token accuracy.

``--sequence-parallel N`` additionally runs the trained model's attention
through ``sequence_parallel_attention`` (ring attention over an N-device
'sp' mesh) and checks it matches the fused kernel — the long-context
scaling path on the same weights.

Run (CPU smoke):
  JAX_PLATFORMS=cpu python example/gluon/transformer_lm.py --steps 60
Long-context check over the virtual mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python example/gluon/transformer_lm.py --steps 30 --sequence-parallel 8
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn


class CausalSelfAttention(gluon.HybridBlock):
    def __init__(self, dim, heads, **kwargs):
        super().__init__(**kwargs)
        assert dim % heads == 0
        self._h = heads
        self._dk = dim // heads
        with self.name_scope():
            self.qkv = nn.Dense(3 * dim, use_bias=False, flatten=False)
            self.out = nn.Dense(dim, use_bias=False, flatten=False)

    def hybrid_forward(self, F, x):
        # x: (B, T, C) -> q/k/v (B, H, T, Dk) -> fused causal attention
        B_T_3C = self.qkv(x)
        q, k, v = F.split(B_T_3C, num_outputs=3, axis=-1)

        def heads(t):
            t = t.reshape((0, 0, self._h, self._dk))
            return F.transpose(t, axes=(0, 2, 1, 3))

        att = F._contrib_flash_attention(heads(q), heads(k), heads(v),
                                         causal=True)
        att = F.transpose(att, axes=(0, 2, 1, 3)).reshape((0, 0, -1))
        return self.out(att)


class Block(gluon.HybridBlock):
    def __init__(self, dim, heads, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.LayerNorm()
            self.attn = CausalSelfAttention(dim, heads)
            self.ln2 = nn.LayerNorm()
            self.mlp = nn.HybridSequential(prefix="")
            self.mlp.add(nn.Dense(4 * dim, activation="relu", flatten=False))
            self.mlp.add(nn.Dense(dim, flatten=False))

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.ln1(x))
        return x + self.mlp(self.ln2(x))


class TransformerLM(gluon.HybridBlock):
    def __init__(self, vocab, dim=64, heads=4, depth=2, max_len=256,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.tok = nn.Embedding(vocab, dim)
            self.pos = nn.Embedding(max_len, dim)
            self.blocks = nn.HybridSequential(prefix="")
            for _ in range(depth):
                self.blocks.add(Block(dim, heads))
            self.ln_f = nn.LayerNorm()
            self.head = nn.Dense(vocab, flatten=False)

    def hybrid_forward(self, F, idx, pos_idx):
        x = self.tok(idx) + self.pos(pos_idx)
        x = self.blocks(x)
        return self.head(self.ln_f(x))


def pattern_batch(rng, batch, T, vocab):
    """Repeating k-grams: the model must learn to copy with period k."""
    x = np.zeros((batch, T + 1), np.int32)
    for i in range(batch):
        k = rng.randint(2, 6)
        motif = rng.randint(0, vocab, k)
        reps = -(-(T + 1) // k)
        x[i] = np.tile(motif, reps)[:T + 1]
    return x[:, :-1], x[:, 1:]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=240)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=16)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--sequence-parallel", type=int, default=0)
    args = ap.parse_args()

    # position table must cover the longer sequence the sp check runs on
    max_len = max(args.seq_len, 8 * args.sequence_parallel)
    net = TransformerLM(args.vocab, dim=args.dim, max_len=max_len)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-3})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)
    pos = nd.array(np.tile(np.arange(args.seq_len), (args.batch_size, 1))
                   .astype(np.int32), dtype="int32")

    first = last = None
    for step in range(args.steps):
        x_np, y_np = pattern_batch(rng, args.batch_size, args.seq_len,
                                   args.vocab)
        x = nd.array(x_np, dtype="int32")
        y = nd.array(y_np.astype(np.float32))
        with autograd.record():
            logits = net(x, pos)          # (B, T, V)
            loss = ce(logits.reshape((-1, args.vocab)),
                      y.reshape((-1,))).mean()
        loss.backward()
        trainer.step(1)
        val = float(loss.asnumpy().sum())
        first = val if first is None else first
        last = val
        if step % 20 == 0:
            print("step %3d loss %.4f" % (step, val), flush=True)

    # next-token accuracy on fresh patterns (after one full period the
    # continuation is determined)
    x_np, y_np = pattern_batch(rng, 16, args.seq_len, args.vocab)
    pos_e = nd.array(np.tile(np.arange(args.seq_len), (16, 1))
                     .astype(np.int32), dtype="int32")
    pred = net(nd.array(x_np, dtype="int32"), pos_e).asnumpy().argmax(-1)
    acc = float((pred[:, 8:] == y_np[:, 8:]).mean())
    print("loss %.3f -> %.3f; next-token accuracy (t>8): %.3f"
          % (first, last, acc))
    assert last < first, "training did not reduce the loss"

    if args.sequence_parallel:
        # long-context scaling: take the TRAINED first block's real q/k/v
        # on a longer sequence and run them through ring attention over an
        # sp mesh — must match the fused kernel the model trained with
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from mxnet_tpu.parallel import sequence_parallel_attention
        from mxnet_tpu.ops.pallas_ops import flash_attention
        n = args.sequence_parallel
        devs = jax.devices()
        assert len(devs) >= n, "need %d devices (set XLA_FLAGS)" % n
        mesh = Mesh(np.array(devs[:n]), ("sp",))
        T = 8 * n
        x_np, _ = pattern_batch(rng, 2, T, args.vocab)
        pos_l = nd.array(np.tile(np.arange(T), (2, 1)).astype(np.int32),
                         dtype="int32")
        blk = net.blocks[0]
        h = blk.ln1(net.tok(nd.array(x_np, dtype="int32")) + net.pos(pos_l))
        heads_ = blk.attn._h
        dk = blk.attn._dk
        qkv_flat = blk.attn.qkv(h).asnumpy()          # (2, T, 3C)
        q_np, k_np, v_np = np.split(qkv_flat, 3, axis=-1)
        qkv = [jnp.asarray(np.transpose(
                   t.reshape(2, T, heads_, dk), (0, 2, 1, 3)))
               for t in (q_np, k_np, v_np)]
        with mesh:
            ring = sequence_parallel_attention(mesh, *qkv, causal=True)
        fused = flash_attention(*qkv, causal=True)
        err = float(jnp.max(jnp.abs(ring - fused)))
        print("ring vs fused attention on trained q/k/v, %d-way sp: "
              "max err %.2e" % (n, err))
        assert np.isfinite(err) and err < 1e-2, err

if __name__ == "__main__":
    main()

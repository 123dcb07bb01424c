"""Decode-capable model contract + a self-contained reference LM.

The decode engine does not wrap arbitrary Gluon blocks: an autoregressive
step needs the model to read and write *paged* KV state, which is a
different calling convention from a stateless batch forward.  A decode
model is any object exposing:

* ``vocab_size`` / ``num_layers`` / ``num_heads`` / ``head_dim`` /
  ``max_len`` attributes (the KV pool geometry comes from these);
* ``param_dict()`` -> ``{name: NDArray}`` — live parameter handles, passed
  straight into the engine's CachedOps;
* ``prefill_fn(params, tokens, length, table, k_pool, v_pool)`` — jax
  arrays in, jax arrays out: tokens ``[1, Lb]`` int32 (padded to a prompt
  bucket), length ``[1]`` int32 (the real prompt length), table ``[1, W]``
  int32 page table.  Runs the whole prompt in one causal pass, scatters
  every position's K/V into the sequence's pages, and returns
  ``(logits [1, V] for position length-1, k_pool', v_pool')``;
* ``decode_fn(params, tokens, positions, tables, k_pool, v_pool)`` — one
  token per slot: tokens ``[S]`` int32, positions ``[S]`` int32 (the cache
  index the new token's K/V lands at), tables ``[S, W]`` int32.  Returns
  ``(logits [S, V], k_pool', v_pool')``.

Both functions must be jax-traceable with **shape-only** signatures (no
data-dependent Python control flow): the engine compiles one CachedOp
signature per (prompt bucket) and per (table width bucket) and steady-state
traffic must never add another.

Two optional entry points unlock chunked prefill and speculative decoding
(the engine falls back to ``prefill_fn``/``decode_fn`` when absent):

* ``chunk_prefill_fn(params, tokens, start, length, table, k_pool,
  v_pool)`` — one fixed-size prompt chunk: tokens ``[1, C]`` int32, start
  ``[1]`` int32 (absolute position of the chunk's first token), length
  ``[1]`` int32 (real tokens in this chunk).  Attends to cache positions
  ``0..start+i`` through the page table (earlier chunks' K/V is READ from
  the pool, which is what makes cross-request prefix reuse bitwise-sound),
  scatters this chunk's K/V, and returns logits for row ``length-1``.
* ``verify_fn(params, tokens, positions, valids, tables, k_pool,
  v_pool)`` — the speculative verify step: tokens ``[S, K+1]`` int32 (the
  committed token followed by K draft proposals), positions ``[S]`` int32
  (cache index of the first token), valids ``[S]`` int32 (rows beyond
  ``valids[s]`` write to the trash block and are ignored).  Returns logits
  ``[S, K+1, V]`` — row ``i`` is the model's next-token distribution after
  the first ``i+1`` tokens, so the engine accepts the longest prefix where
  proposal ``i`` equals ``argmax(row i-1)``.

Because a fixed kernel *shape* pins the XLA tiling, all-chunked prefill and
all-verify decode reproduce the sequential reference bitwise only when the
reference itself runs through the SAME chunk/verify signatures (one row
valid at a time).  ``DecodeEngine.generate_reference`` does exactly that.

Exactness contract (the bitwise gate in tests/test_decode.py leans on it):
dead slots and page-table padding use masks whose excluded weights are
EXACTLY zero (``exp(-inf) == 0``), and every per-slot computation is
row-independent — so a slot's logits are bit-identical whether its
neighbors are live, dead, or absent, and whatever table width bucket the
scheduler picked.  ``TinyCausalLM`` is the in-tree reference
implementation: a small pre-norm transformer (learned positions, weight-
tied unembedding) used by the tests, the chaos scenarios, and
``tools/serve_bench.py --profile decode``.
"""
from __future__ import annotations

import numpy as np

__all__ = ["TinyCausalLM"]


def _rms(x):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + 1e-6)


class TinyCausalLM:
    """Small causal transformer LM with paged-KV prefill/decode kernels."""

    def __init__(self, vocab_size=48, hidden=32, num_layers=2, num_heads=2,
                 max_len=128, seed=0, eos_id=None, context_attention=None,
                 params=None):
        if hidden % num_heads:
            raise ValueError("hidden must divide into num_heads")
        # name of a bound mesh axis ('sp') to split prompt attention over
        # via the fused ulysses/ring kernels; requires running inside
        # ShardedDecodeModel(sp=n).  None = the bitwise dense path.
        self.context_attention = context_attention
        self.vocab_size = int(vocab_size)
        self.hidden = int(hidden)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = self.hidden // self.num_heads
        self.max_len = int(max_len)
        self.eos_id = eos_id
        from ... import ndarray as nd
        expected = self.param_shapes(self.vocab_size, self.hidden,
                                     self.num_layers, self.max_len)
        if params is not None:
            # checkpoint-loaded weights (serving/deploy.py builds each new
            # generation this way) — validate against the geometry before
            # anything can compile a kernel over a half-shaped model
            if set(params) != set(expected):
                missing = sorted(set(expected) - set(params))
                extra = sorted(set(params) - set(expected))
                raise ValueError("params key mismatch: missing %r extra %r"
                                 % (missing, extra))
            loaded = {}
            for k, shape in expected.items():
                arr = params[k]
                if tuple(arr.shape) != shape:
                    raise ValueError("param %r has shape %r, expected %r"
                                     % (k, tuple(arr.shape), shape))
                loaded[k] = arr if isinstance(arr, nd.NDArray) \
                    else nd.array(np.asarray(arr, np.float32))
            self._params = loaded
            return
        rng = np.random.RandomState(seed)
        scale = 1.0 / np.sqrt(self.hidden)

        def w(*shape):
            return nd.array(rng.randn(*shape).astype(np.float32) * scale)

        self._params = {k: w(*shape) for k, shape in expected.items()}

    @staticmethod
    def param_shapes(vocab_size, hidden, num_layers, max_len):
        """``{name: shape}`` of every parameter of that geometry."""
        shapes = {"embed": (vocab_size, hidden), "pos": (max_len, hidden)}
        for l in range(num_layers):
            for role in ("wq", "wk", "wv", "wo"):
                shapes["l%d_%s" % (l, role)] = (hidden, hidden)
            shapes["l%d_w1" % l] = (hidden, 2 * hidden)
            shapes["l%d_w2" % l] = (2 * hidden, hidden)
        return shapes

    def param_dict(self):
        return dict(self._params)

    def partition_specs(self):
        """Weight sharding over the serving mesh's 'tp' axis (consumed by
        serving.decode.sharding.ShardedDecodeModel): attention and MLP
        projections split on a hidden-sized axis — always divisible, since
        the head count must divide tp and hidden = heads * head_dim."""
        from jax.sharding import PartitionSpec as P
        specs = {"embed": P(None, "tp"), "pos": P(None, "tp")}
        for l in range(self.num_layers):
            specs["l%d_wq" % l] = P(None, "tp")
            specs["l%d_wk" % l] = P(None, "tp")
            specs["l%d_wv" % l] = P(None, "tp")
            specs["l%d_wo" % l] = P("tp", None)
            specs["l%d_w1" % l] = P(None, "tp")
            specs["l%d_w2" % l] = P("tp", None)
        return specs

    # ------------------------------------------------------------------
    def _qkv(self, p, l, x, n_rows):
        h, d = self.num_heads, self.head_dim
        q = (x @ p["l%d_wq" % l]).reshape(n_rows, h, d)
        k = (x @ p["l%d_wk" % l]).reshape(n_rows, h, d)
        v = (x @ p["l%d_wv" % l]).reshape(n_rows, h, d)
        return q, k, v

    def _mlp(self, p, l, h):
        import jax
        return h + jax.nn.gelu(_rms(h) @ p["l%d_w1" % l]) @ p["l%d_w2" % l]

    def prefill_fn(self, p, tokens, length, table, k_pool, v_pool):
        """Causal pass over one padded prompt; scatters K/V into pages."""
        import jax.numpy as jnp
        bs = k_pool.shape[2]
        L = tokens.shape[1]
        t = tokens[0]
        h = p["embed"][t] + p["pos"][:L]                       # [L, H]
        idx = jnp.arange(L)
        blk = table[0, idx // bs]
        off = idx % bs
        # causal mask: position i attends j <= i; prompt padding sits at
        # j >= length > i for every real row, so it is excluded for free
        causal = idx[None, :] <= idx[:, None]                  # [L, L]
        for l in range(self.num_layers):
            q, k, v = self._qkv(p, l, _rms(h), L)
            # pad-row K/V lands in the trash block / the tail of the
            # sequence's own last block — positions the attention mask
            # never admits before a decode write overwrites them
            k_pool = k_pool.at[l, blk, off].set(k)
            v_pool = v_pool.at[l, blk, off].set(v)
            if self.context_attention is None:
                scores = jnp.einsum("ihd,jhd->hij", q, k) \
                    / jnp.sqrt(float(self.head_dim)).astype(q.dtype)
                scores = jnp.where(causal[None], scores, -jnp.inf)
                w = _softmax(scores)
                att = jnp.einsum("hij,jhd->ihd", w, v).reshape(
                    L, self.hidden)
            else:
                att = self._fused_context_attention(q, k, v, causal)
            h = h + att @ p["l%d_wo" % l]
            h = self._mlp(p, l, h)
        last = _rms(h[length[0] - 1])
        logits = last @ p["embed"].T
        return logits[None], k_pool, v_pool

    def _fused_context_attention(self, q, k, v, causal):
        """Whole-prompt attention through the fused sequence-parallel
        kernels (sharding.long_context_attention): the sequence axis
        splits over the ``context_attention`` mesh axis, Ulysses when the
        head count divides it, streaming ring otherwise.  Allclose — NOT
        bitwise — to the dense path (both kernels mask with -1e30 and the
        ring streams its softmax), and only traceable inside a shard_map
        that binds the axis (ShardedDecodeModel(sp=n)).  Prompt buckets
        the axis extent does not divide run the dense math below."""
        import jax.numpy as jnp
        from .sharding import long_context_attention
        L = q.shape[0]

        def dense(q4, k4, v4):
            s = jnp.einsum("bhid,bhjd->bhij", q4, k4) \
                / jnp.sqrt(float(self.head_dim)).astype(q4.dtype)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            return jnp.einsum("bhij,bhjd->bhid", _softmax(s), v4)

        q4, k4, v4 = (jnp.transpose(x, (1, 0, 2))[None]
                      for x in (q, k, v))
        att4 = long_context_attention(q4, k4, v4, causal=True,
                                      axis_name=self.context_attention,
                                      fallback=dense)
        return jnp.transpose(att4[0], (1, 0, 2)).reshape(L, self.hidden)

    def decode_fn(self, p, tokens, positions, tables, k_pool, v_pool):
        """One fixed-shape decode step for every slot (live or dead)."""
        import jax.numpy as jnp
        bs = k_pool.shape[2]
        S = tokens.shape[0]
        W = tables.shape[1]
        T = W * bs
        srow = jnp.arange(S)
        h = p["embed"][tokens] + p["pos"][positions]           # [S, H]
        blk = tables[srow, positions // bs]
        off = positions % bs
        # valid cache positions: 0..positions[s] inclusive (the new token
        # attends to itself); excluded weights are EXACTLY zero, so table
        # padding and stale pool contents cannot perturb live slots
        mask = jnp.arange(T)[None, :] <= positions[:, None]    # [S, T]
        for l in range(self.num_layers):
            q, k, v = self._qkv(p, l, _rms(h), S)
            k_pool = k_pool.at[l, blk, off].set(k)
            v_pool = v_pool.at[l, blk, off].set(v)
            kseq = k_pool[l][tables].reshape(S, T, self.num_heads,
                                             self.head_dim)
            vseq = v_pool[l][tables].reshape(S, T, self.num_heads,
                                             self.head_dim)
            scores = jnp.einsum("shd,sthd->sht", q, kseq) \
                / jnp.sqrt(float(self.head_dim)).astype(q.dtype)
            scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
            w = _softmax(scores)
            att = jnp.einsum("sht,sthd->shd", w, vseq).reshape(
                S, self.hidden)
            h = h + att @ p["l%d_wo" % l]
            h = self._mlp(p, l, h)
        logits = _rms(h) @ p["embed"].T
        return logits, k_pool, v_pool

    def chunk_prefill_fn(self, p, tokens, start, length, table, k_pool,
                         v_pool):
        """One prompt chunk at absolute positions start..start+C-1.

        Earlier chunks are consumed through the page table (gathered from
        the pool, not recomputed), so a chunk run on top of another
        request's shared prefix pages produces bit-identical K/V and
        logits to a private from-scratch chunked run — the property the
        copy-on-write prefix cache banks on.
        """
        import jax.numpy as jnp
        bs = k_pool.shape[2]
        C = tokens.shape[1]
        W = table.shape[1]
        T = W * bs
        t = tokens[0]
        pos = start[0] + jnp.arange(C)                     # absolute
        h = p["embed"][t] + p["pos"][jnp.clip(pos, 0, self.max_len - 1)]
        blk = table[0, pos // bs]
        off = pos % bs
        valid = jnp.arange(C) < length[0]
        blk = jnp.where(valid, blk, 0)                     # pad -> trash
        # pad rows clamp to position 0 (attend j <= 0): finite garbage,
        # the same dead-slot discipline as decode_fn.  An all-False mask
        # row would softmax to NaN and poison the trash block.
        epos = jnp.where(valid, pos, 0)
        mask = jnp.arange(T)[None, :] <= epos[:, None]     # [C, T]
        for l in range(self.num_layers):
            q, k, v = self._qkv(p, l, _rms(h), C)
            k_pool = k_pool.at[l, blk, off].set(k)
            v_pool = v_pool.at[l, blk, off].set(v)
            kseq = k_pool[l][table[0]].reshape(T, self.num_heads,
                                               self.head_dim)
            vseq = v_pool[l][table[0]].reshape(T, self.num_heads,
                                               self.head_dim)
            scores = jnp.einsum("ihd,jhd->hij", q, kseq) \
                / jnp.sqrt(float(self.head_dim)).astype(q.dtype)
            scores = jnp.where(mask[None], scores, -jnp.inf)
            w = _softmax(scores)
            att = jnp.einsum("hij,jhd->ihd", w, vseq).reshape(
                C, self.hidden)
            h = h + att @ p["l%d_wo" % l]
            h = self._mlp(p, l, h)
        last = _rms(h[length[0] - 1])
        logits = last @ p["embed"].T
        return logits[None], k_pool, v_pool

    def verify_fn(self, p, tokens, positions, valids, tables, k_pool,
                  v_pool):
        """Speculative verify: K+1 tokens per slot in one fixed-shape call.

        Row ``i`` of slot ``s`` is the committed/proposed token at cache
        position ``positions[s] + i``; rows at or past ``valids[s]`` write
        to the trash block and attend position 0 only (finite garbage —
        see chunk_prefill_fn).  Per-row outputs depend only on that row's
        token, its position, and masked pool content, so a verify call
        with one valid row reproduces ``generate_reference`` bitwise and
        extra proposal rows never perturb the accepted prefix.
        """
        import jax.numpy as jnp
        bs = k_pool.shape[2]
        S, K1 = tokens.shape
        W = tables.shape[1]
        T = W * bs
        pos = positions[:, None] + jnp.arange(K1)[None, :]   # [S, K1]
        valid = jnp.arange(K1)[None, :] < valids[:, None]
        h = p["embed"][tokens] \
            + p["pos"][jnp.clip(pos, 0, self.max_len - 1)]   # [S, K1, H]
        blk = jnp.take_along_axis(tables, pos // bs, axis=1)
        blk = jnp.where(valid, blk, 0)                       # -> trash
        off = pos % bs
        epos = jnp.where(valid, pos, 0)
        mask = jnp.arange(T)[None, None, :] <= epos[:, :, None]
        for l in range(self.num_layers):
            x = _rms(h)
            q = (x @ p["l%d_wq" % l]).reshape(S, K1, self.num_heads,
                                              self.head_dim)
            k = (x @ p["l%d_wk" % l]).reshape(S, K1, self.num_heads,
                                              self.head_dim)
            v = (x @ p["l%d_wv" % l]).reshape(S, K1, self.num_heads,
                                              self.head_dim)
            k_pool = k_pool.at[l, blk, off].set(k)
            v_pool = v_pool.at[l, blk, off].set(v)
            kseq = k_pool[l][tables].reshape(S, T, self.num_heads,
                                             self.head_dim)
            vseq = v_pool[l][tables].reshape(S, T, self.num_heads,
                                             self.head_dim)
            scores = jnp.einsum("sihd,sjhd->shij", q, kseq) \
                / jnp.sqrt(float(self.head_dim)).astype(q.dtype)
            scores = jnp.where(mask[:, None, :, :], scores, -jnp.inf)
            w = _softmax(scores)
            att = jnp.einsum("shij,sjhd->sihd", w, vseq).reshape(
                S, K1, self.hidden)
            h = h + att @ p["l%d_wo" % l]
            h = self._mlp(p, l, h)
        logits = _rms(h) @ p["embed"].T                      # [S, K1, V]
        return logits, k_pool, v_pool

    def propose_fn(self, p, tokens, positions, tables, k_pool, v_pool,
                   num_tokens):
        """Greedy draft proposer: ``num_tokens`` unrolled decode steps with
        the argmax on-device, so one compiled call yields K proposals.
        ``num_tokens`` is static (baked into the signature).  Returns
        (proposals ``[S, num_tokens]`` int32, k_pool', v_pool')."""
        import jax.numpy as jnp
        cur = tokens
        pos = positions
        outs = []
        for _ in range(int(num_tokens)):
            logits, k_pool, v_pool = self.decode_fn(
                p, cur, pos, tables, k_pool, v_pool)
            cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            outs.append(cur)
            pos = pos + 1
        return jnp.stack(outs, axis=1), k_pool, v_pool


def _softmax(scores):
    """Max-shifted softmax over the last axis with exact-zero masking:
    ``exp(-inf - finite_max) == 0`` exactly, so masked positions contribute
    nothing to the normalizer regardless of the padded width."""
    import jax.numpy as jnp
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)

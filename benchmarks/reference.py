"""The plain reference of both configurations: DALL-E's forward pass, its loss
and its optimizer step in straightforward ``jax.numpy`` and float32.

It follows lucidrains/DALLE-pytorch v1.0.8 (``dalle_pytorch.py``,
``transformer.py``, ``attention.py``): unique padding ids and <bos>, text and
image embeddings concatenated, per block ``x + scale * f(shift(norm(x)))`` for
attention and then for a GEGLU feed-forward, the three-part rotary table (in
float32, as the source keeps it) on q, k AND v or, with ``rotary_emb`` off,
learned text positions and a row + column table over the image grid, a
static may-attend mask per attention pattern, the final norm,
the vocabulary head, and the loss weighted 1 : 7 between text and image. No
kernel, no cache, no batching trick; it imports nothing of the program and
reads only the parameter values that ``weights.py`` drew from the seed.

``mode`` selects the arithmetic: ``f32`` is the reference (every matmul at
``highest``); ``int8`` and ``fp8`` are the CONTROLS, the same code computed
in the nearest precision below the bfloat16 the configurations state
(int8: weights per output channel, embeddings per row, K and V per token and
head, as a weight-only int8 serving path stores them; fp8: both operands of
every matmul in float8_e4m3 under a per-tensor scale).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import costs

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6
NEG = -0.7 * float(np.finfo(np.float32).max)


# ------------------------------------------------------------ arithmetic


def _fake_int8(w, axis):
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _fake_fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _weight(w, mode):
    w = w.astype(jnp.float32)
    if mode == "int8":
        return _fake_int8(w, axis=0)
    if mode == "fp8":
        return _fake_fp8(w)
    return w


def _act(x, mode):
    return _fake_fp8(x) if mode == "fp8" else x


def _mm(x, w, mode):
    return jnp.matmul(_act(x, mode), _weight(w, mode), precision=HIGHEST)


def _embed(table, ids, mode):
    table = table.astype(jnp.float32)
    if mode == "int8":
        table = _fake_int8(table, axis=1)
    return table[ids]


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + LN_EPS)
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


# ---------------------------------------------------------------- rotary


def _angles(positions, freqs):
    a = np.einsum("i,j->ij", np.asarray(positions, np.float64), freqs)
    return np.repeat(a, 2, axis=-1)


@functools.lru_cache(maxsize=None)
def rotary_table(dim_head: int, tl: int, fmap: int) -> np.ndarray:
    """(tl + fmap**2 - 1, dim_head) angles: a third of the rotated channels
    carry the 1-D text position (image pinned at 8192), two thirds the 2-D
    pixel position on [-1, 1] (text pinned at -10); the rest rotate by 0."""
    rot = dim_head // 3
    lang = 1.0 / (10000.0 ** (np.arange(0, rot, 2)[: rot // 2] / rot))
    pix = np.linspace(1.0, 10.0 / 2, rot // 2) * np.pi
    n_img = fmap * fmap
    part_text = np.concatenate(
        (_angles(np.arange(tl), lang), _angles(np.full(n_img, 8192.0), lang))
    )
    axial = _angles(np.linspace(-1.0, 1.0, fmap), pix)
    rows = np.broadcast_to(axial[:, None], (fmap, fmap, axial.shape[-1]))
    cols = np.broadcast_to(axial[None, :], (fmap, fmap, axial.shape[-1]))
    img_2d = np.concatenate((rows, cols), axis=-1).reshape(n_img, -1)
    text_2d = np.tile(_angles(np.full(tl, -10.0), pix), (1, 2))
    table = np.concatenate(
        (part_text, np.concatenate((text_2d, img_2d))), axis=-1
    )[:-1]
    pad = dim_head - table.shape[-1]
    return np.pad(table, ((0, 0), (0, pad))).astype(np.float32)


def _rotate(t, table):
    """t: (b, n, h, d). Adjacent pairs (x1, x2) -> (-x2, x1)."""
    pairs = t.reshape(t.shape[:-1] + (-1, 2))
    half = jnp.stack((-pairs[..., 1], pairs[..., 0]), axis=-1).reshape(t.shape)
    ang = jnp.asarray(table)[None, : t.shape[1], None, :]
    return t * jnp.cos(ang) + half * jnp.sin(ang)


# ------------------------------------------------------------ token shift


def _shift(x, tl: int, fmap: int):
    """Text: the first half of the channels comes from the previous token.
    Image (on its grid): the first quarter from the token one row up, the
    second quarter from the token one column left."""
    b, n, d = x.shape
    n_img = fmap * fmap
    pad = tl + n_img - n
    text, img = x[:, :tl], x[:, tl:]
    img = jnp.pad(img, ((0, 0), (0, pad), (0, 0))).reshape(b, fmap, fmap, d)
    half = d // 2
    text = jnp.concatenate(
        (jnp.pad(text[..., :half], ((0, 0), (1, 0), (0, 0)))[:, :-1], text[..., half:]),
        axis=-1,
    )
    q = d // 4
    top = jnp.pad(img[..., :q], ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :-1]
    left = jnp.pad(img[..., q : 2 * q], ((0, 0), (0, 0), (1, 0), (0, 0)))[:, :, :-1]
    img = jnp.concatenate((top, left, img[..., 2 * q :]), axis=-1)
    img = img.reshape(b, n_img, d)[:, : n_img - pad]
    return jnp.concatenate((text, img), axis=1)


# ---------------------------------------------------------------- blocks


def _inner(p: dict) -> dict:
    """The projections of a block, below however many wrappers name 'fn'."""
    while "fn" in p and not ("to_qkv" in p or "Dense_0" in p):
        p = p["fn"]
    return p


def _attention(x, p, cfg, kind, mode):
    b, n, _ = x.shape
    h, d = cfg["heads"], cfg["dim_head"]
    qkv = _mm(x, p["to_qkv"]["kernel"], mode)
    q, k, v = (t.reshape(b, n, h, d) for t in jnp.split(qkv, 3, axis=-1))
    if cfg["rotary_emb"]:
        table = rotary_table(d, costs.text_len(cfg), cfg["image_fmap_size"])
        q, k, v = (_rotate(t, table) for t in (q, k, v))
    if mode == "int8":
        k, v = _fake_int8(k, axis=-1), _fake_int8(v, axis=-1)
    scores = jnp.einsum(
        "bihd,bjhd->bhij", _act(q * d ** -0.5, mode), _act(k, mode), precision=HIGHEST
    )
    mask = jnp.asarray(costs.pattern_mask(cfg, kind)[:n, :n])
    attn = jax.nn.softmax(jnp.where(mask[None, None], scores, NEG), axis=-1)
    out = jnp.einsum("bhij,bjhd->bihd", _act(attn, mode), _act(v, mode), precision=HIGHEST)
    out = out.reshape(b, n, h * d)
    return _mm(out, p["to_out"]["kernel"], mode) + p["to_out"]["bias"].astype(jnp.float32)


def _feed_forward(x, p, mode):
    hidden = _mm(x, p["Dense_0"]["kernel"], mode) + p["Dense_0"]["bias"].astype(jnp.float32)
    value, gates = jnp.split(hidden, 2, axis=-1)
    hidden = value * jax.nn.gelu(gates, approximate=True)
    return _mm(hidden, p["Dense_1"]["kernel"], mode) + p["Dense_1"]["bias"].astype(jnp.float32)


def hidden_states(params, cfg: dict, text, image, mode: str = "f32"):
    """text: (b, text_seq_len) raw ids, 0 = padding; image: (b, m) token ids,
    m <= fmap**2. Returns the final-normed hidden states (b, n, dim) of the
    n = min(text_seq_len + 1 + m, text_seq_len + fmap**2) positions fed."""
    tsl, tl, fmap = cfg["text_seq_len"], costs.text_len(cfg), cfg["image_fmap_size"]
    pad_ids = jnp.arange(tsl, dtype=text.dtype) + cfg["num_text_tokens"]
    ids = jnp.pad(jnp.where(text == 0, pad_ids, text), ((0, 0), (1, 0)))
    x = _embed(params["text_emb"]["embedding"], ids, mode)
    if image.shape[1]:
        x = jnp.concatenate(
            (x, _embed(params["image_emb"]["embedding"], image, mode)), axis=1
        )
    if not cfg["rotary_emb"]:
        x = x.at[:, : tl].add(params["text_pos_emb"]["embedding"].astype(jnp.float32)[None])
        if image.shape[1]:
            pos = params["image_pos_emb"]
            grid = (pos["row_emb"] + pos["col_emb"]).astype(jnp.float32).reshape(fmap * fmap, -1)
            x = x.at[:, tl:].add(grid[None, : image.shape[1]])
    x = x[:, : costs.seq_len(cfg)]
    blocks = params["transformer"]
    for i, kind in enumerate(costs.layer_kinds(cfg)):
        for name in ("attn", "ff"):
            p = blocks[f"{name}_{i}"]
            y = _layer_norm(x, p["fn"]["LayerNorm_0"])
            if cfg["shift_tokens"]:
                y = _shift(y, tl, fmap)
            proj = _inner(p["fn"])
            y = (
                _attention(y, proj, cfg, kind, mode) if name == "attn"
                else _feed_forward(y, proj, mode)
            )
            x = x + y * p["scale"].astype(jnp.float32)
    return _layer_norm(x, params["final_norm"]), ids


def image_logits(params, cfg: dict, text, image, mode: str = "f32"):
    """Logits over the IMAGE vocabulary at every position that predicts an
    image token: (b, m, num_image_tokens) for m image tokens served. Row i
    predicts image token i, from the prompt and the tokens before it."""
    m = image.shape[1]
    normed, _ = hidden_states(params, cfg, text, image[:, : max(m - 1, 0)], mode)
    ext = costs.text_vocab(cfg)
    head = params["to_logits"]
    rows = normed[:, cfg["text_seq_len"] :]
    return _mm(rows, head["kernel"][:, ext:], mode) + head["bias"][ext:].astype(jnp.float32)


def loss(params, cfg: dict, text, image, mode: str = "f32"):
    """The weighted cross-entropy: text positions predict the next text id
    over the text vocabulary, image positions the image token over the image
    vocabulary, means weighted 1 : loss_img_weight."""
    normed, ids = hidden_states(params, cfg, text, image, mode)
    ext, tsl = costs.text_vocab(cfg), cfg["text_seq_len"]
    head = params["to_logits"]
    bias = head["bias"].astype(jnp.float32)

    def mean_nll(rows, kernel, bias, labels):
        logits = _mm(rows, kernel, mode) + bias
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)

    loss_text = mean_nll(normed[:, :tsl], head["kernel"][:, :ext], bias[:ext], ids[:, 1:])
    loss_img = mean_nll(normed[:, tsl:], head["kernel"][:, ext:], bias[ext:], image)
    w = cfg["loss_img_weight"]
    return (loss_text + w * loss_img) / (w + 1)


# ------------------------------------------------------------- optimizer


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads)))
    factor = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree_util.tree_map(lambda g: g * factor, grads)


def adam_update(grads, mu, nu, count: int, lr: float,
                b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    """One Adam step. Returns (parameter deltas, mu, nu); ``count`` is the
    number of steps taken INCLUDING this one."""
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = tm(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    delta = tm(
        lambda m, v: -lr * (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count)) + eps),
        mu, nu,
    )
    return delta, mu, nu

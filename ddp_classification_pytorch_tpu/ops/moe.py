"""Expert-parallel mixture-of-experts FFN — dropless, exact, mesh-sharded.

The reference has no MoE (SURVEY §2.2 lists EP as absent); this op extends
the framework's parallelism pentad (DP / class-TP / ring-SP / GPipe-PP) with
expert parallelism over the same `model` mesh axis. Design choices, TPU-
first:

- **Split-FFN experts**: the transformer block's 4·C-hidden MLP is split
  into E experts of hidden H = 4·C/E each, so total parameters and dense
  FLOPs match the standard block — routing redistributes capacity instead
  of adding it.
- **Dense dispatch, sparse gates**: every expert runs every token (one big
  batched einsum on the MXU — no sorting, no capacity factor, no dropped
  tokens); sparsity lives in the top-k router gates that weight the
  combine. Exact by construction, static-shaped, and immune to the
  load-balancing pathologies of capacity-based dispatch. The all-to-all
  dispatch that skips non-routed FLOPs is the classic next optimization;
  at split-FFN sizes the MXU prefers the dense batched matmul anyway.
- **Expert parallelism**: under a >1 `model` axis, each device holds E/N
  experts (leading-dim sharded params), computes their weighted outputs
  for all tokens, and one `psum` over the axis completes the combine —
  the EP collective. Tokens stay replicated along the model axis (the
  axis serves ONE role per config: class-TP | SP | PP | EP).
- Router math in f32 (softmax over expert logits); expert matmuls in the
  model's compute dtype with f32 accumulation.

`sparse_moe` is the dispatch core for many narrow experts (the token
decoder, models/decoder_lm.py): dense dispatch of top-6 of 64 would cost
10.7x the active FLOPs. It routes over the router's full width, sorts the
token-slots by expert, runs grouped matmuls (`jax.lax.ragged_dot`: one
kernel over the ragged groups on the TPU) over the experts HELD HERE only,
and combines. Two spaces: the S = k·N SLOTS in choice-major order (slot
j·N + n is token n's j-th choice, so (S, C) ↔ (k, N, C) is a free reshape)
and the ROWS sorted by expert that the grouped matmuls work on; `order`
takes a row to its slot, `inv` a slot to its row. Gathers cross between
them, never scatters. From an N-row source to the rows: the dispatch forward
(`_dispatch_rows`: u[order % N]), again when remat recomputes it, and the
combine backward (`_combine`: the output's cotangent g[order % N], whose
products with the weights and with y stay among the rows). From the row
buffer to the slots, the dearer kind: the combine forward (y[inv]) and the
dispatch backward (d_rows[inv]); the combine's weight gradients cross as S
scalars.

Dropless, whatever the load. How many rows the buffer has is chosen from the
shapes (`slot_bound`): `SLOT_BOUND_FACTOR` times what a uniform router would
send to the experts held here, at most S. Holding a quarter of the experts
or more, that is S, the static worst case (every slot on a held expert), and
the code is one path. Holding fewer, `_bounded_experts` asks the counted
`load` on the device (`lax.cond`): a step and layer whose load fits works on
the first `bound` sorted rows, which hold every held expert's whole group
(`order` sorts the others last); one whose load does not fit walks the live
rows in windows of `bound`, as many as the load needs, and adds the windows'
results. The same terms either way (within a window in today's order), no
slot is dropped, and no branch holds a row buffer of S rows: the two
slot-space results stay (S, C), in both. It is told
which experts it holds (`first_expert`, the banks' leading dimension), so
one chip of an expert-parallel layout runs it without the exchange and a
`model` axis > 1 runs the same function per shard with the psum above (each
shard asks its own load: the psum stands outside the `cond`).
The ViT's split-FFN path above still dispatches densely (ROADMAP D6).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..utils.compat import shard_map_unchecked


def router_logits(x: jnp.ndarray, router_w: jnp.ndarray) -> jnp.ndarray:
    """(B, T, C) tokens × (C, E) router → (B, T, E) f32 logits. Computed
    ONCE per block; gates and the balance penalty both derive from it."""
    return jnp.einsum("btc,ce->bte", x.astype(jnp.float32),
                      router_w.astype(jnp.float32))


def topk_gates(logits: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """(B, T, E) router logits → (B, T, E) gate weights: softmax over the
    top-k logits per token, zero elsewhere (renormalized sparse mixture)."""
    e = logits.shape[-1]
    if not 1 <= top_k <= e:
        raise ValueError(f"top_k={top_k} must be in [1, num_experts={e}]")
    vals, idx = jax.lax.top_k(logits, top_k)              # (B, T, k)
    w = jax.nn.softmax(vals, axis=-1)
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)    # (B, T, k, E)
    return jnp.einsum("btk,btke->bte", w, onehot)


def load_balance_loss(logits: jnp.ndarray, top_k: int) -> jnp.ndarray:
    """Switch-Transformer-style router balance penalty, scalar ≥ ~1.

    E · Σ_e f_e · p_e, where f_e is the fraction of tokens whose top-k set
    contains expert e and p_e the mean full-softmax router probability of e.
    Equals 1·top_k under a perfectly uniform router and grows as routing
    collapses onto few experts; differentiable through p_e (f_e is a
    stop-gradient count, the standard estimator). Dense dispatch makes
    collapse a quality problem rather than a capacity-overflow problem —
    this keeps the mixture diverse either way."""
    e = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)               # (B, T, E)
    _, idx = jax.lax.top_k(logits, top_k)
    chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(axis=2)  # (B,T,E)
    f = jax.lax.stop_gradient(chosen.reshape(-1, e).mean(axis=0))
    p = probs.reshape(-1, e).mean(axis=0)
    return e * jnp.sum(f * p)


def _expert_mix(x, gates, w_in, b_in, w_out, b_out, dtype):
    """Weighted sum of local experts' FFN outputs for all tokens.

    x (B, T, C); gates (B, T, e_local); experts leading-dim e_local.
    Returns (B, T, C) f32 partial combine (summed over local experts).
    """
    xc = x.astype(dtype)
    h = jnp.einsum("btc,ech->beth", xc, w_in.astype(dtype),
                   preferred_element_type=jnp.float32)
    h = jax.nn.gelu(h + b_in[None, :, None, :])
    y = jnp.einsum("beth,ehc->betc", h.astype(dtype), w_out.astype(dtype),
                   preferred_element_type=jnp.float32)
    y = y + b_out[None, :, None, :]
    return jnp.einsum("betc,bte->btc", y, gates.astype(jnp.float32))


def moe_mlp(
    x: jnp.ndarray,
    gates: jnp.ndarray,
    w_in: jnp.ndarray,
    b_in: jnp.ndarray,
    w_out: jnp.ndarray,
    b_out: jnp.ndarray,
    *,
    dtype=jnp.bfloat16,
    mesh: Optional[Mesh] = None,
    axis: Optional[str] = None,
    batch_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Mixture-of-experts FFN, optionally expert-sharded over `axis`.

    x: (B, T, C); gates: (B, T, E) from `topk_gates` (computed once by the
    caller, so the router einsum/top-k isn't re-evaluated inside the
    shard_map); w_in: (E, C, H); b_in: (E, H); w_out: (E, H, C);
    b_out: (E, C). Returns (B, T, C) in x.dtype. Sharded and unsharded
    paths are numerically identical (test-pinned): distribution decides
    where experts live, never the math.
    """
    e = w_in.shape[0]
    if gates.shape[-1] != e:
        # the sharded path's dynamic_slice would clamp a wrong width into
        # silently wrong output — reject it here for both paths
        raise ValueError(
            f"gates width {gates.shape[-1]} != num experts {e}")
    n = mesh.shape[axis] if (mesh is not None and axis) else 1
    if n <= 1:
        out = _expert_mix(x, gates, w_in, b_in, w_out, b_out, dtype)
        return out.astype(x.dtype)
    if e % n:
        raise ValueError(f"num experts {e} not divisible by axis size {n}")

    def body(x, gates, w_in, b_in, w_out, b_out):
        idx = jax.lax.axis_index(axis)
        e_local = w_in.shape[0]
        g_local = jax.lax.dynamic_slice_in_dim(
            gates, idx * e_local, e_local, axis=2)
        part = _expert_mix(x, g_local, w_in, b_in, w_out, b_out, dtype)
        return jax.lax.psum(part, axis)                   # EP combine

    x_spec = P(batch_axis, None, None) if batch_axis else P(None, None, None)
    f = shard_map_unchecked(
        body, mesh=mesh,
        in_specs=(x_spec, x_spec, P(axis, None, None), P(axis, None),
                  P(axis, None, None), P(axis, None)),
        out_specs=x_spec,
    )
    return f(x, gates, w_in, b_in, w_out, b_out).astype(x.dtype)


# ---------------------------------------------------------------------------
# sparse dropless dispatch over the experts held here
# ---------------------------------------------------------------------------

def route_top_k(logits: jnp.ndarray, top_k: int, *, scoring: str = "softmax",
                bias: Optional[jnp.ndarray] = None, scale: float = 1.0,
                eps: float = 0.0, n_group: int = 1, topk_group: int = 1):
    """(N, E) f32 router logits → (expert ids (N, k) i32, weights (N, k)
    f32). "softmax": the k largest logits and the softmax over those k (=
    the full softmax renormalised over the chosen). "sigmoid" (DeepSeek-V3):
    scores s = sigmoid(logits); the k largest of s + `bias` are chosen —
    the bias (E,) steers selection and nothing else, its gradient is zero —
    and the weights are the chosen experts' UNBIASED scores, renormalised
    over the chosen (their sum + `eps`: LFM2 publishes 1e-6, DeepSeek-V3
    none) and times `scale`. With `n_group` > 1 the choice is group-limited
    (DeepSeek-V3's `noaux_tc`): the experts stand in `n_group` groups of
    E / n_group, a group's score is the sum of its two largest s + bias, and
    the k largest are taken inside the `topk_group` best groups only."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        vals, idx = jax.lax.top_k(logits, top_k)
        return idx.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)
    if scoring != "sigmoid":
        raise ValueError(f"unknown router scoring {scoring!r}")
    scores = jax.nn.sigmoid(logits)
    biased = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    if n_group > 1:
        biased = _group_limited(biased, n_group, topk_group)
    # the k largest by k passes of argmax (ties to the lower id, as top_k's),
    # each pass reading its expert's unbiased score through the same one-hot
    # mask: no sort, and no gather whose transpose is a scatter-add into
    # (N, E)
    picked, chosen = [], []
    for _ in range(top_k):
        best = jnp.argmax(biased, axis=-1)
        hit = jax.nn.one_hot(best, biased.shape[-1], dtype=bool)
        picked.append(best)
        chosen.append(jnp.sum(jnp.where(hit, scores, 0.0), axis=-1))
        biased = jnp.where(hit, -jnp.inf, biased)
    idx, chosen = jnp.stack(picked, axis=-1), jnp.stack(chosen, axis=-1)
    total = jnp.sum(chosen, axis=-1, keepdims=True)
    # no `+ 0.0` in the program of a router that publishes no epsilon
    weights = scale * chosen / (total + eps if eps else total)
    return idx.astype(jnp.int32), weights


def _group_limited(biased: jnp.ndarray, n_group: int, topk_group: int):
    """(N, E) selection scores with every expert outside the `topk_group`
    best of the `n_group` groups at −inf. A group's score is the sum of its
    two largest entries; like the choice below, by passes of argmax under a
    one-hot mask: no sort, nothing to transpose."""
    n, e = biased.shape
    per = jax.lax.stop_gradient(biased).reshape(n, n_group, e // n_group)
    first = jnp.max(per, axis=-1)
    hit = jax.nn.one_hot(jnp.argmax(per, axis=-1), per.shape[-1], dtype=bool)
    score = first + jnp.max(jnp.where(hit, -jnp.inf, per), axis=-1)   # (N, groups)
    keep = jnp.zeros(score.shape, bool)
    for _ in range(topk_group):
        hit = jax.nn.one_hot(jnp.argmax(score, axis=-1), n_group, dtype=bool)
        keep, score = keep | hit, jnp.where(hit, -jnp.inf, score)
    return jnp.where(keep[:, :, None], per, -jnp.inf).reshape(n, e)


# How many sorted rows the experts held here work on, as a multiple of what a
# uniform router would send them. The largest counted load a routing layer has
# shown is 2.1 x that share (2,160 slots for 1,024 in `ling3_ep64_8k`, 16,900
# for 8,192 in `joyai_ep16_8k`, seeded routers with a selection bias; PERF.md
# §5), `lfm2_ep4_8k`'s heaviest seed 1.3 x. A load past the bound is not
# dropped: that step and layer walks its rows in windows (`_bounded_experts`).
SLOT_BOUND_FACTOR = 4


def slot_bound(slots: int, held: int, experts: int) -> int:
    """Rows of the sorted buffer for `slots` = k·N token-slots routed over
    `experts`, of which `held` are here: `SLOT_BOUND_FACTOR` uniform shares,
    up to whole tiles of 128 rows, and at most `slots` (= no bound: every
    slot may fall on a held expert)."""
    share = -(-SLOT_BOUND_FACTOR * slots * held // experts)
    return min(slots, -(-share // 128) * 128)


def _at_slots(rows, inv):
    """rows[inv]: for every slot, its sorted row. A bounded buffer is shorter
    than `inv` reaches: a slot that is `mine` lies inside it; the others
    point past its end, are clipped here and masked by the caller, as the
    rows past the last group are."""
    if rows.shape[0] == inv.shape[0]:
        return rows[inv]
    return jnp.take(rows, inv, axis=0, mode="clip")


@jax.custom_vjp
def _dispatch_rows(u, order, inv, mine):
    """The token's row of u (N, C) for every sorted row: slot s belongs to
    token s % N (slots are laid out choice-major, s = j·N + n, so that
    (S, C) ↔ (k, N, C) is a free reshape). `order` is a permutation of the
    S = k·N slots, or its first rows, and `inv` the permutation's inverse, so
    the transpose is a gather too (no scatter-add): d_u[n] = Σ_j d_rows[inv[
    j·N + n]] over the slots `mine` — the rows of the others lie past the
    last group, where the grouped matmuls' transposes leave whatever the
    buffer held."""
    return u[order % u.shape[0]]


def _dispatch_fwd(u, order, inv, mine):
    return u[order % u.shape[0]], (inv, mine, u.shape[0])


def _dispatch_bwd(res, g):
    inv, mine, n = res
    g = jnp.where(mine[:, None], _at_slots(g, inv), jnp.zeros((), g.dtype))
    return g.reshape(-1, n, g.shape[-1]).sum(axis=0), None, None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, w, order, inv, mine):
    """out[n] = Σ_j w[j, n] · y[inv[j·N + n]] (N, C) f32 over the slots
    `mine`: the sorted rows y weighted back onto their tokens; `order` has y's
    length. w (k, N) f32 is zero where not `mine`; the mask stays all the
    same, since those slots' rows lie past the last group and were never
    written.

    The backward stays among the sorted rows: the token's cotangent for every
    sorted row is one gather from the N-row g, g_tok = g[order % N]; d_y is
    its product with the row's weight (in f32, rounded once) and d_w the
    row-wise dot of g_tok with y, brought to slot order as S scalars.
    Nothing of shape (k, N, C) is built, and nothing reads the forward's
    y[inv]: under remat it is not computed a second time."""
    k, n = w.shape
    slots = jnp.where(mine[:, None], _at_slots(y, inv), jnp.zeros((), y.dtype))
    return (w[..., None] * slots.reshape(k, n, -1).astype(jnp.float32)).sum(axis=0)


def _combine_fwd(y, w, order, inv, mine):
    return _combine(y, w, order, inv, mine), (y, w, order, inv, mine)


def _combine_bwd(res, g):
    y, w, order, inv, mine = res
    g_tok = g[order % w.shape[1]]                         # (rows, C) f32
    d_y = (w.reshape(-1)[order][:, None] * g_tok).astype(y.dtype)
    # a row past the last group may hold anything, NaN too: its dot is
    # dropped here, and its d_y above is 0 · g
    dots = (g_tok * y.astype(jnp.float32)).sum(axis=-1)
    d_w = jnp.where(mine, _at_slots(dots, inv), 0.0).reshape(w.shape)
    return d_y, d_w, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


# the gate of a gated unit: ReGLU | SwiGLU
GATE_ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def _experts_on_rows(u, w, order, inv, mine, load, w_gate, w_up, w_down, *,
                     dtype, activation):
    """The tokens u (N, C) through the held experts and back, weighted by
    the router's w (N, k): (N, C) f32. `order` is the sorted rows to work on:
    all S, the first `bound`, or a window of them (`inv`, `mine` and `load`
    then in the window's terms); `load` says where each expert's group ends
    among them."""
    n, top_k = w.shape
    with jax.named_scope("moe.dispatch"):
        rows = _dispatch_rows(u.astype(dtype), order, inv, mine)
    with jax.named_scope("moe.experts"):
        def grouped(x, bank):
            return jax.lax.ragged_dot(x, bank, load, preferred_element_type=dtype)

        # a gated unit without bias (ReGLU | SwiGLU); gate and up as one
        # grouped matmul over the banks side by side. Rows past the last
        # group belong to no expert held here; what the kernels leave in
        # them is not defined and nothing below reads it
        width = w_gate.shape[-1]
        both = grouped(rows, jnp.concatenate(
            [w_gate.astype(dtype), w_up.astype(dtype)], axis=-1))
        y = grouped(GATE_ACTIVATIONS[activation](both[:, :width])
                    * both[:, width:], w_down.astype(dtype))
    with jax.named_scope("moe.combine"):
        w = jnp.where(mine.reshape(top_k, n), w.T, 0.0)
        return _combine(y, w, order, inv, mine)


def _window(first, rows, u, w, order, inv, mine, load, *banks, **kw):
    """`_experts_on_rows` on the sorted rows [first, first + rows) alone:
    each expert's group cut to its part inside the window, and of the slots
    `mine` those whose row lies there. Windows that tile the live rows add up
    to the whole layer, every slot in exactly one. (The last window of a
    buffer that is no whole number of them starts early; the rows it repeats
    are computed and, their slots not being its own, weigh nothing.)"""
    start = jnp.minimum(first, order.shape[0] - rows)
    ends = jnp.cumsum(load)
    part = (jnp.clip(ends, start, start + rows)
            - jnp.clip(ends - load, start, start + rows))
    inside = mine & (inv >= first) & (inv < start + rows)
    return _experts_on_rows(
        u, w, jax.lax.dynamic_slice(order, (start,), (rows,)), inv - start,
        inside, part, *banks, **kw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bounded_experts(bound, dtype, activation, u, w, order, inv, mine, load,
                     w_gate, w_up, w_down):
    """`_experts_on_rows` with a buffer of `bound` sorted rows, whatever the
    load: one `cond` on the device. Where the counted load fits, the first
    `bound` rows hold every held expert's whole group (`order` sorts the
    others last) and are the layer. Where it does not, the live rows are
    walked in windows of `bound` (`_window`), as many as the load needs, and
    the windows' results added: no slot dropped, no array of S rows of the
    model's width in either branch but the two slot-space gathers' results.

    A `custom_vjp` so that each branch's residuals stay inside the branch:
    differentiated as it stands, the forward `cond` would hand out both
    branches' residuals, zeros for the branch not taken, in every layer of
    every step, and the loop would keep every window's. The backward holds a
    `cond` of its own and rebuilds the taken branch's forward inside it from
    the inputs (what `--remat` does for the layer anyway)."""
    return _bounded_fwd(bound, dtype, activation, u, w, order, inv, mine, load,
                        w_gate, w_up, w_down)[0]


def _branches(bound, order, inv, mine, load, **kw):
    """(fits, window): the layer on the first `bound` sorted rows, and on the
    i-th window of `bound` of them, as functions of (u, w, *banks)."""
    def fits(u, w, *banks):
        return _experts_on_rows(u, w, order[:bound], inv, mine, load, *banks, **kw)

    def window(i, u, w, *banks):
        return _window(i * bound, bound, u, w, order, inv, mine, load, *banks, **kw)

    return fits, window


def _bounded_fwd(bound, dtype, activation, u, w, order, inv, mine, load, *banks):
    fits, window = _branches(bound, order, inv, mine, load, dtype=dtype,
                             activation=activation)

    def windows(*a):
        return jax.lax.fori_loop(0, -(-load.sum() // bound),
                                 lambda i, out: out + window(i, *a),
                                 jnp.zeros(u.shape, jnp.float32))

    out = jax.lax.cond(load.sum() <= bound, fits, windows, u, w, *banks)
    return out, (u, w, order, inv, mine, load, *banks)


def _bounded_bwd(bound, dtype, activation, res, g):
    u, w, order, inv, mine, load, *banks = res
    fits, window = _branches(bound, order, inv, mine, load, dtype=dtype,
                             activation=activation)

    def pull(layer, g, *a):
        return jax.vjp(layer, *a)[1](g)

    def windows(g, *a):
        # the windows' gradients added in float32, then as `fits` hands them
        like = jax.eval_shape(functools.partial(pull, fits), g, *a)
        total = jax.lax.fori_loop(
            0, -(-load.sum() // bound),
            lambda i, acc: jax.tree_util.tree_map(
                lambda t, d: t + d.astype(jnp.float32), acc,
                pull(functools.partial(window, i), g, *a)),
            jax.tree_util.tree_map(lambda d: jnp.zeros(d.shape, jnp.float32), like))
        return jax.tree_util.tree_map(lambda t, d: t.astype(d.dtype), total, like)

    d_u, d_w, *d_banks = jax.lax.cond(
        load.sum() <= bound, functools.partial(pull, fits), windows, g, u, w, *banks)
    return (d_u, d_w, None, None, None, None, *d_banks)


_bounded_experts.defvjp(_bounded_fwd, _bounded_bwd)


def _sparse_experts(u, logits, w_gate, w_up, w_down, *, top_k, first_expert,
                    dtype, activation="relu", route=None):
    held = w_gate.shape[0]
    with jax.named_scope("moe.route"):
        idx, w = route_top_k(logits, top_k, **(route or {}))
    with jax.named_scope("moe.dispatch"):
        local = idx.T.reshape(-1) - first_expert          # (S,) S = k·N
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)                # others sort last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        load = (key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :]
                ).sum(axis=0, dtype=jnp.int32)            # slots per expert
    bound = slot_bound(key.shape[0], held, logits.shape[-1])
    if bound == key.shape[0]:
        out = _experts_on_rows(u, w, order, inv, mine, load, w_gate, w_up, w_down,
                               dtype=dtype, activation=activation)
    else:
        out = _bounded_experts(bound, dtype, activation, u, w, order, inv, mine,
                               load, w_gate, w_up, w_down)
    return out, load


def sparse_moe(
    u: jnp.ndarray,
    logits: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    top_k: int,
    first_expert: int = 0,
    dtype=jnp.bfloat16,
    mesh: Optional[Mesh] = None,
    axis: Optional[str] = None,
    batch_axis: Optional[str] = None,
    activation: str = "relu",
    route: Optional[dict] = None,
):
    """Sparse mixture of gated experts (`activation` on the gate: "relu" =
    ReGLU, "silu" = SwiGLU) for the tokens u (N, C).

    `logits` (N, E) are the router's, over ALL E experts, handed in by the
    caller (the decoder takes them before or after attention); `route` are
    `route_top_k`'s keywords (scoring, bias, scale, eps). The banks w_gate /
    w_up (e, C, H) and w_down (e, H, C) are the e experts held here, ids
    `first_expert .. first_expert + e − 1`; slots routed elsewhere add
    nothing. Returns (y (N, C) f32 = Σ over the chosen held experts of
    weight · expert(u), load (e,) i32 token-slots each held expert took).

    Under a `model` axis > 1 the banks are sharded on their leading
    dimension, every shard computes its own experts' part and one psum
    completes the sum (`load` comes back for all the banks' experts in
    order; with `batch_axis` the tokens stay sharded over it and the loads
    are summed over it)."""
    e = w_gate.shape[0]
    if not first_expert + e <= logits.shape[-1]:
        raise ValueError(
            f"experts {first_expert}..{first_expert + e - 1} are not among "
            f"the router's {logits.shape[-1]}")
    route = dict(route or {})
    bias = route.pop("bias", None)

    def core(u, logits, w_gate, w_up, w_down, bias, first_expert):
        return _sparse_experts(
            u, logits, w_gate, w_up, w_down, top_k=top_k, dtype=dtype,
            first_expert=first_expert, activation=activation,
            route=route if bias is None else dict(route, bias=bias))

    n_shards = mesh.shape[axis] if (mesh is not None and axis) else 1
    if n_shards <= 1:
        return core(u, logits, w_gate, w_up, w_down, bias, first_expert)
    if e % n_shards:
        raise ValueError(f"num experts {e} not divisible by axis size {n_shards}")

    def body(u, logits, w_gate, w_up, w_down, bias):
        first = first_expert + jax.lax.axis_index(axis) * w_gate.shape[0]
        part, load = core(u, logits, w_gate, w_up, w_down, bias, first)
        if batch_axis:
            load = jax.lax.psum(load, batch_axis)
        return jax.lax.psum(part, axis), load             # EP combine

    bank, rows = P(axis, None, None), P(batch_axis, None)
    return shard_map_unchecked(
        body, mesh=mesh, in_specs=(rows, rows, bank, bank, bank, P()),
        out_specs=(rows, P(axis)))(u, logits, w_gate, w_up, w_down, bias)

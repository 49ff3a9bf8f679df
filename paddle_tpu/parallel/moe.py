"""Expert parallelism: all-to-all token dispatch over the 'ep' mesh axis.

reference: python/paddle/incubate/distributed/models/moe/moe_layer.py
(MoEScatter:99 / MoEGather:149 — all-to-all PyLayers over the expert
communicator), distributed/utils/moe_utils.py global_scatter/global_gather,
SPMD rule paddle/phi/infermeta/spmd_rules/moe_gate_dispatch.cc.

TPU-native design (GShard): capacity-bounded dispatch with STATIC shapes —
every (expert, capacity) slot exists whether or not a token fills it, so XLA
compiles one fixed program and `lax.all_to_all` rides the ICI. Inside
shard_map each ep-rank holds E/ep experts and B/ep tokens:

  1. top-k gate -> per-token expert choice + in-expert position (cumsum)
  2. scatter tokens into the local [E, C] dispatch buffer
  3. all_to_all: [E, C] -> each rank gets its experts' slots from every rank
  4. run local experts on [E_local, ep*C]
  5. all_to_all back + combine with gate weights

Dropped tokens (over capacity) contribute zero — GShard semantics.

`dropless_moe` is the other kind: a routed layer that is told which
experts it holds (`experts_held = (first, count)`), routes over all of
them and computes its own experts' part of the result, with room for every
assignment that can land on a held expert. One shard of an expert-parallel
group runs it as it stands; the exchange between shards is not written yet.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["moe_dispatch_combine", "ExpertParallelMoE", "gshard_dispatch",
           "route_top_k", "grouped_matmul", "dropless_moe"]


def gshard_dispatch(x, gate_logits, num_experts, capacity, top_k=2):
    """Local (single-shard) GShard dispatch.

    x: [T, D] tokens; gate_logits: [T, E].
    Returns (dispatched [E, C, D], combine_weights [T, E, C], probs [T, E]).
    """
    T, D = x.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)                 # [T, k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert queue
    # one-hot over experts per choice, cumulative over flattened (k, T)
    # order: choice 0 of all tokens first (GShard prioritizes top-1)
    flat_exp = jnp.swapaxes(topi, 0, 1).reshape(-1)          # [k*T]
    flat_gate = jnp.swapaxes(topv, 0, 1).reshape(-1)         # [k*T]
    onehot = jax.nn.one_hot(flat_exp, num_experts, dtype=jnp.int32)  # [kT, E]
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot      # 1-based
    pos = (pos_in_expert.sum(-1) - 1)                        # [kT], 0-based
    keep = pos < capacity
    flat_gate = jnp.where(keep, flat_gate, 0.0)
    pos = jnp.clip(pos, 0, capacity - 1)

    token_ids = jnp.tile(jnp.arange(T), top_k)               # [kT]
    dispatched = jnp.zeros((num_experts, capacity, D), x.dtype)
    dispatched = dispatched.at[flat_exp, pos].add(
        jnp.where(keep[:, None], x[token_ids], 0))

    combine = jnp.zeros((T, num_experts, capacity), x.dtype)
    combine = combine.at[token_ids, flat_exp, pos].add(
        flat_gate.astype(x.dtype))
    return dispatched, combine, probs


def moe_dispatch_combine(x, gate_logits, expert_apply, expert_params,
                         num_experts, mesh=None, axis_name="ep",
                         capacity_factor=1.25, top_k=2):
    """Full EP MoE: dispatch -> all_to_all -> local experts -> all_to_all
    -> combine. Call inside jit; when `mesh` has an `axis_name` axis the
    token and expert dims shard over it (E % ep == 0 required).

    x: [T, D]; gate_logits: [T, E];
    expert_params: pytree whose leaves have a leading expert dim E
      (sharded over ep when mesh is given);
    expert_apply(params_for_one_expert, tokens [C', D]) -> [C', D].
    """
    T, D = x.shape
    capacity = max(1, int(math.ceil(top_k * T / num_experts * capacity_factor)))

    if mesh is None or axis_name not in mesh.axis_names:
        dispatched, combine, probs = gshard_dispatch(
            x, gate_logits, num_experts, capacity, top_k)
        outs = jnp.stack([
            expert_apply(jax.tree_util.tree_map(lambda w: w[e], expert_params),
                         dispatched[e])
            for e in range(num_experts)])                    # [E, C, D]
        out = jnp.einsum("tec,ecd->td", combine, outs)
        return out, probs

    ep = mesh.shape[axis_name]
    assert num_experts % ep == 0, "num_experts must divide the ep axis"
    e_local = num_experts // ep
    # capacity is per (shard, expert): derive from the LOCAL token count so
    # buffers/all-to-all volume don't scale with ep and drop semantics match
    # the dense path
    capacity = max(1, int(math.ceil(
        top_k * (T // ep) / num_experts * capacity_factor)))

    def local(x_shard, logits_shard, local_params):
        # x_shard: [T/ep, D] — each rank dispatches its own tokens;
        # local_params leaves: [e_local, ...] — this rank's experts
        dispatched, combine, probs = gshard_dispatch(
            x_shard, logits_shard, num_experts, capacity, top_k)
        # [E, C, D]: exchange so each rank receives ITS experts' slots from
        # every rank. tiled all_to_all splits axis 0 into ep chunks and
        # concatenates the received chunks on the same axis.
        d = jax.lax.all_to_all(dispatched, axis_name, split_axis=0,
                               concat_axis=0, tiled=True)    # [E, C, D]
        # received layout: [src_rank * e_local + e][c] — regroup per expert
        d = d.reshape(ep, e_local, capacity, D)
        d = jnp.swapaxes(d, 0, 1).reshape(e_local, ep * capacity, D)
        outs = jnp.stack([
            expert_apply(jax.tree_util.tree_map(lambda w: w[i], local_params),
                         d[i])
            for i in range(e_local)])                        # [e_local, ep*C, D]
        # route back: inverse regroup + all_to_all
        o = outs.reshape(e_local, ep, capacity, D)
        o = jnp.swapaxes(o, 0, 1).reshape(ep * e_local, capacity, D)
        o = jax.lax.all_to_all(o, axis_name, split_axis=0, concat_axis=0,
                               tiled=True)                   # [E, C, D] (mine)
        out = jnp.einsum("tec,ecd->td", combine, o)
        return out, probs

    pspecs = jax.tree_util.tree_map(
        lambda w: P(axis_name, *([None] * (w.ndim - 1))), expert_params)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name, None), pspecs),
        out_specs=(P(axis_name, None), P(axis_name, None)))(
        x, gate_logits, expert_params)


class ExpertParallelMoE:
    """Functional EP-MoE block for SpmdTrainer-style training loops.

    params: gate [D, E]; w1 [E, D, H]; w2 [E, H, D]  (sharded Shard(0) on ep)
    """

    def __init__(self, d_model, d_hidden, num_experts, mesh=None,
                 axis_name="ep", top_k=2, capacity_factor=1.25,
                 activation=jax.nn.gelu):
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.mesh = mesh
        self.axis_name = axis_name
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = activation

    def init(self, key, dtype=jnp.float32):
        kg, k1, k2 = jax.random.split(key, 3)
        s = 1.0 / math.sqrt(self.d_model)
        return {
            "gate": jax.random.normal(kg, (self.d_model, self.num_experts),
                                      dtype) * s,
            "w1": jax.random.normal(
                k1, (self.num_experts, self.d_model, self.d_hidden), dtype) * s,
            "w2": jax.random.normal(
                k2, (self.num_experts, self.d_hidden, self.d_model),
                dtype) / math.sqrt(self.d_hidden),
        }

    def apply(self, params, x):
        """x: [T, D] -> ([T, D], aux_loss)."""
        logits = x @ params["gate"]

        def expert_apply(w, tokens):
            return self.activation(tokens @ w["w1"]) @ w["w2"]

        out, probs = moe_dispatch_combine(
            x, logits, expert_apply, {"w1": params["w1"], "w2": params["w2"]},
            self.num_experts, self.mesh, self.axis_name,
            self.capacity_factor, self.top_k)
        # GShard load-balance auxiliary loss
        me = probs.mean(axis=0)                              # [E]
        top1 = jnp.argmax(logits, axis=-1)
        ce = jnp.mean(
            jax.nn.one_hot(top1, self.num_experts, dtype=probs.dtype), axis=0)
        aux = self.num_experts * jnp.sum(me * ce)
        return out, aux


# -- dropless routed experts ---------------------------------------------------

def route_top_k(x, router_w, top_k):
    """(expert ids [T, k], gates [T, k] float32): the top-k of the router's
    logits over ALL its outputs, gates = softmax over the k chosen logits.
    That is also the routing of the configs that key it `norm_topk_prob`
    (softmax over all outputs, top-k of the probabilities, weights
    renormalised over the k): exp is monotone, so the top-k is the same,
    and p_e / sum of the k p's = exp(l_e) / sum of the k exp(l)'s, the
    full softmax's denominator cancelling (tests/test_mellum.py).
    Logits accumulate in float32 so that near-ties break as they would in
    a float32 reference."""
    logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
    _, top_ids = jax.lax.top_k(logits, top_k)
    # the chosen logits picked out by comparison, not taken from top_k:
    # the gradient is then a sum over a mask and not a scatter-add
    chosen = top_ids[..., None] == jnp.arange(logits.shape[-1])
    top_logits = jnp.sum(jnp.where(chosen, logits[..., None, :], 0), axis=-1)
    return top_ids, jax.nn.softmax(top_logits, axis=-1)


def sorted_assignments(top_ids, experts_held):
    """The (token, choice) assignments that land on held experts, sorted by
    expert into `rows` = T * min(k, count) rows: no token can have more
    held choices than that, so every one of them has a row, whatever the
    routing. Returns (source [rows]: the flat assignment t * k + j a row
    holds; slot [T, k]: the row of each assignment, `rows` where its
    expert is not held; sizes [count]: rows of each held expert). Rows
    past sum(sizes) hold assignments to experts that are not held."""
    first, count = experts_held
    tokens, k = top_ids.shape
    rows = tokens * min(k, count)
    local = top_ids - first
    # an expert that is not held sorts last, under the id `count`
    flat = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    # counted by comparison: a bincount is a scatter-add, which a TPU runs
    # one assignment after the other
    sizes = jnp.sum(flat[:, None] == jnp.arange(count), axis=0,
                    dtype=jnp.int32)
    slot = jnp.where(flat < count, jnp.argsort(order), rows)
    return order[:rows], slot.reshape(tokens, k).astype(jnp.int32), sizes


def _gather_rows(y, slot):
    """out[i] = sum_j y[slot[i, j]], leaving out slots past y's rows. One
    gather a column of `slot`, summed in float32: the [T, k, D] array a
    single gather would make is k times the tokens' own size."""
    rows = y.shape[0]
    out = jnp.zeros((slot.shape[0], y.shape[1]), jnp.float32)
    for j in range(slot.shape[1]):
        s = slot[:, j]
        out = out + jnp.where((s < rows)[:, None],
                              y[jnp.minimum(s, rows - 1)], 0)
    return out.astype(y.dtype)


# Dispatch (tokens into expert order) and combine (expert order back onto
# tokens) are each other's transposes, and both are gathers: a row belongs
# to one assignment and an assignment has one row. Autodiff would write
# either transpose as a scatter-add of tens of thousands of rows, which a
# TPU runs far slower than the gather.

@jax.custom_vjp
def _dispatch(x, index, slot):
    return x[index]


def _dispatch_fwd(x, index, slot):
    return x[index], (index, slot)


def _dispatch_bwd(res, g):
    index, slot = res
    return _combine(g, index, slot), None, None


@jax.custom_vjp
def _combine(y, index, slot):
    return _gather_rows(y, slot)


def _combine_fwd(y, index, slot):
    return _gather_rows(y, slot), (index, slot)


def _combine_bwd(res, g):
    index, slot = res
    return _dispatch(g, index, slot), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)
_combine.defvjp(_combine_fwd, _combine_bwd)


# One number an assignment (its gate) goes into expert order by a sort and
# comes back by a sort: a gather of single numbers costs a TPU as much an
# index as a gather of whole rows does.

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _in_slot_order(values, slot, rows):
    """out[slot[a]] = values[a] for the flat assignments a that have one of
    the `rows` rows, and after them the others' values, in their own order
    (`sorted_assignments`' order). Only rows that hold an assignment send
    a gradient back: the others' is whatever the kernels left there."""
    return _in_slot_order_fwd(values, slot, rows)[0]


def _in_slot_order_fwd(values, slot, rows):
    _, order, out = jax.lax.sort(
        (slot, jnp.arange(slot.shape[0], dtype=slot.dtype), values),
        num_keys=1)
    return out[:rows], (order, slot)


def _in_slot_order_bwd(rows, res, g):
    order, slot = res
    g = jnp.pad(g, (0, order.shape[0] - rows))
    return jnp.where(slot < rows,
                     jax.lax.sort((order, g), num_keys=1)[1], 0), None


_in_slot_order.defvjp(_in_slot_order_fwd, _in_slot_order_bwd)


# Tiles (rows, k, n) of jax's Pallas grouped matmul (megablox `gmm`, and
# `tgmm` for the weights' gradient) for bf16 on a TPU, by the (k, n) of
# one expert's matrix: (forward, the rows' gradient, the weights'
# gradient). Each is its product's fastest of `tools/moe_rows_bench.py
# --sweep` on a v5e (PERF.md section 6, PR 35): for `gmm` the contraction
# whole, so that no accumulator is read back, beside as much of the
# matrix as VMEM holds twice; for `tgmm` the largest float32 tile of the
# gradient that fits. XLA's own kernel behind `lax.ragged_dot` runs tiles
# of 512 x 256 x 256 at these widths and is bound by its grid steps, not
# the MXU: over the six products at 512 rows a group 31 TFLOP/s where
# these read 91 or more, at 2,048 rows 51 and 142. Widths that were not
# measured keep `lax.ragged_dot`.
_GMM_TILES = {
    (2304, 1792): ((256, 2304, 896), (256, 1792, 1152), (256, 1152, 896)),
    (896, 2304): ((256, 896, 2304), (256, 2304, 896), (256, 896, 1152)),
}


def _gmm_tiles(lhs, rhs):
    """_GMM_TILES' entry for these operands of `grouped_matmul`, or None
    where `lax.ragged_dot` stays: off a TPU, in another dtype, at widths
    with no entry, or where a tile does not divide the buffer's rows."""
    tiles = _GMM_TILES.get(tuple(rhs.shape[1:]))
    if (tiles is None or jax.default_backend() != "tpu"
            or lhs.dtype != jnp.bfloat16 or rhs.dtype != jnp.bfloat16
            or any(lhs.shape[0] % rows for rows, _, _ in tiles)):
        return None
    return tiles


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, sizes, tiles, interpret):
    """`grouped_matmul` through the Pallas kernels, each of the three
    products at its own tiles (megablox's own `custom_vjp` gives all three
    the forward's). float32 accumulation, results in the operands' dtype:
    what `lax.ragged_dot` gives. Rows past the last group are not written,
    forward or backward, as with XLA's kernel."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    return gmm(lhs, rhs, sizes, lhs.dtype, tiles[0], interpret=interpret)


def _gmm_fwd(lhs, rhs, sizes, tiles, interpret):
    return _gmm(lhs, rhs, sizes, tiles, interpret), (lhs, rhs, sizes)


def _gmm_bwd(tiles, interpret, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    lhs, rhs, sizes = res
    d_lhs = gmm(g, rhs, sizes, lhs.dtype, tiles[1], transpose_rhs=True,
                interpret=interpret)
    # tgmm takes its first operand (k, rows) and turns it back itself
    d_rhs = tgmm(lhs.swapaxes(0, 1), g, sizes, rhs.dtype, tiles[2],
                 interpret=interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, sizes):
    """out[rows of group g] = lhs[rows of group g] @ rhs[g]: lhs [rows, k]
    sorted by group, rhs [groups, k, n], sizes [groups] the rows of each.
    Chosen from the operands' shapes and dtype as the call is traced:
    jax's Pallas grouped matmul at the tiles of `_GMM_TILES` where there
    is an entry, `lax.ragged_dot` (XLA's kernel on a TPU) everywhere
    else. The same sums either way."""
    tiles = _gmm_tiles(lhs, rhs)
    if tiles is None:
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    # off a TPU (a test that hands out tiles) the kernels are interpreted
    return _gmm(lhs, rhs, sizes, tiles, jax.default_backend() != "tpu")


def dropless_moe(x, router_w, w_in, w_out, top_k, experts_held,
                 differentiate_routing=True):
    """The held experts' part of a routed gated-MLP layer.

    x [T, D]; router_w [D, E] over ALL E experts; w_in [count, D, 2 * I]
    and w_out [count, I, D] of the `count` experts held here, which are
    experts `first .. first + count - 1` of the E; experts_held = (first,
    count).

        out[t] = sum, over those of t's top-k experts e that are held, of
                 gate[t, e] * (silu(x W_in[e][:, :I]) * x W_in[e][:, I:]) W_out[e]

    What the experts that are not held would add is left out: over the
    shards of an expert-parallel group the parts add up to the whole layer
    (tests/test_granite_moe_hybrid.py). Nothing is dropped: assignments
    are sorted by expert, and the grouped product (`grouped_matmul`: a
    kernel that visits only the tiles that hold rows, jax's Pallas one
    at the widths it was measured at, else `lax.ragged_dot`, which the
    TPU compiler turns into its own: on the chip a call costs the same in
    a buffer of 4,096 rows as in one of 18,432, and its rate follows the
    rows a group has, PERF.md section 6, PR 29 and PR 35) has a row for
    every assignment that can land here. Rows past the last assignment
    are not written by those kernels and may hold anything, NaN too:
    nothing may carry them on, forward or backward. No part of the layer
    is a scatter, and no gather fetches single numbers: a TPU pays both
    by the index. Component scope `pt.moe.route` holds what is not expert
    work.

    `differentiate_routing=False` makes the gates constants of the
    backward pass: the router's weight gets no gradient and the routing
    sends none to x. A share of the experts cannot give that gradient:
    the terms of the experts that are not held are missing from it, and
    with the held terms alone it sends every token to the held experts
    (PERF.md section 6, PR 34)."""
    tokens, k = x.shape[0], top_k
    with jax.named_scope("pt.moe.route"):
        top_ids, gates = route_top_k(x, router_w, k)
        if not differentiate_routing:
            gates = jax.lax.stop_gradient(gates)
        source, slot, sizes = sorted_assignments(top_ids, experts_held)
        rows = source.shape[0]
        live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        token = source // k
        gate_rows = _in_slot_order(gates.reshape(-1), slot.reshape(-1),
                                   rows)[:, None]
        held = min(k, experts_held[1])
        if held < k:
            # a token's rows first: it has `held` at most, so the combine
            # gathers that many columns and not one a choice
            slot = jnp.sort(slot, axis=1)[:, :held]
        xs = _dispatch(x, token, slot)
    inter = w_out.shape[1]
    # rows past the last assignment hold whatever the kernel left there
    h = jnp.where(live, grouped_matmul(xs, w_in, sizes), 0)
    act = (jax.nn.silu(h[:, :inter].astype(jnp.float32))
           * h[:, inter:].astype(jnp.float32) * gate_rows).astype(x.dtype)
    y = grouped_matmul(act, w_out, sizes)
    with jax.named_scope("pt.moe.route"):
        return _combine(y, token, slot)

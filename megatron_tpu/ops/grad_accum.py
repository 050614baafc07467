"""A micro-batch's weight gradients, summed into the float32 accumulator
where the backward pass writes them.

The reference's `--gradient_accumulation_fusion` (ref:
`wgrad_gemm_accum_fp32`, fused_weight_gradient_dense.cu:129-157: "dW += dY^T
X" straight into `main_grad`). A step of several micro-batches keeps a
float32 accumulator beside every parameter. Added to it after the backward
pass, a stacked `[layers, ...]` gradient is written whole, read back and
added: a pass over the parameters' bytes once a micro-batch that multiplies
nothing. Here the accumulator goes into the backward pass instead, as a pair
that differentiates to a sum: `join(Pair(w, acc))` is `w` to the forward
pass, and the gradient with respect to `acc` is `acc + dW`. `train_step`
differentiates a micro-batch's loss with respect to the accumulators, and
what comes back are the new ones.

Outside a loop the pair is one function, `summed`. Over a stack of layers
(`scan`) it is two, because the accumulators ride the loop as its CARRY:
layer `l` passes them through `attach` with its slice of the parameters, which
hands the slice's gradient to row `l` of theirs, and the loop's result is
`seeded` with them, which starts their gradient at the accumulators
themselves. Differentiated, the backward loop over layers carries the
accumulators' gradient, which starts as the accumulators and to which layer
`l` adds `dW_l` at row `l`, in place: XLA makes that sum the output of the
product that computes `dW_l`. No stack of gradients exists, and no second
copy of the accumulators. (As the scanned `xs` of that loop the sums are
stacked into a new array, which a copy of the whole stack brings back into
the micro-batch loop's carry: 8 bytes a parameter where the add took 12.
Carried through untouched and indexed, `lax.scan` makes the accumulators a
constant of the loop, whose gradient it sums from zero and adds afterwards:
today's pass. PERF.md section 6, PR 44.)

`train_step` says which accumulator belongs to which leaf while it traces a
micro-batch's loss (`accumulating`), and the code that first takes a leaf
asks for it there (`pairs`): `transformer.py`'s scans over layers, and
`language_model.loss_fn` for the leaves outside the stacks. A leaf is found by
what it IS (the tracer `train_step` handed the loss), so a forward that hands
its stacks to `transformer.py` untouched takes this way whatever the model,
and one that rebuilds its leaves first (a custom head, merged LoRA weights)
does not: `train_step` adds to the accumulators what nobody took, as it
always did. Outside `accumulating` (serving, evaluation, the pipelined steps,
a step of one micro-batch) nothing is found and every function here returns
what it was given: those programs trace as they did.

Whoever takes a leaf's accumulator joins every element of the leaf, once:
an element left out comes back without its accumulator, one joined twice
with two of them.
"""
from __future__ import annotations

import contextlib
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Pair(NamedTuple):
    """A parameter leaf beside its accumulator: a pytree node, so that
    `jax.tree.map` slices and reshapes both."""
    w: jax.Array
    acc: jax.Array


def _is_pair(x):
    return isinstance(x, Pair)


@jax.custom_vjp
def summed(w, acc):
    """`w`; the gradient of `acc` is `acc` plus `w`'s."""
    return w


summed.defvjp(lambda w, acc: (w, acc),
              lambda acc, dw: (None, acc + dw.astype(jnp.float32)))


@jax.custom_vjp
def attach(w, accs, row):
    """(`w`, `accs`); `w`'s gradient is added to row `row` of `accs`'."""
    return w, accs


def _attach_bwd(row, cts):
    dw, daccs = cts
    # the row keeps its leading 1 from slice to update: with a squeeze
    # between them XLA (TPU) cuts the row out in a pass of its own and
    # does not read it inside the product's fusion
    at_row = jax.lax.dynamic_slice_in_dim(daccs, row, 1)
    return None, jax.lax.dynamic_update_slice_in_dim(
        daccs, at_row + dw[None].astype(jnp.float32), row, 0), None


attach.defvjp(lambda w, accs, row: ((w, accs), row), _attach_bwd)


@jax.custom_vjp
def seeded(x, accs):
    """`x`; the gradient of `accs` starts as `accs` themselves."""
    return x


seeded.defvjp(lambda x, accs: (x, accs), lambda accs, dx: (dx, accs))

_CTX = threading.local()


@contextlib.contextmanager
def accumulating(params, accs, taken: set):
    """While a micro-batch's loss is traced: `accs` (float32, `params`'
    structure) are the accumulators of `params`' leaves, or None for none.
    `taken` fills with the places, among `jax.tree.leaves(params)`, of the
    leaves whose accumulators were taken: their gradients reach the caller
    as `accs`' own, already added to them."""
    if accs is None:
        yield
        return
    prev = getattr(_CTX, "cur", None)
    by_id = {id(w): (i, acc) for i, (w, acc) in enumerate(
        zip(jax.tree.leaves(params), jax.tree.leaves(accs)))}
    _CTX.cur = (by_id, taken)
    try:
        yield
    finally:
        _CTX.cur = prev


def pairs(tree):
    """`tree` with every leaf that has an accumulator to give as a `Pair`
    (the leaf itself no longer differentiated: its gradient is the
    accumulator's), for `scan` or `join`."""
    cur = getattr(_CTX, "cur", None)
    if cur is None:
        return tree
    by_id, taken = cur

    def take(w):
        i, acc = by_id.get(id(w), (None, None))
        if acc is None or i in taken:
            return w
        taken.add(i)
        return Pair(jax.lax.stop_gradient(w), acc)
    return jax.tree.map(take, tree)


def join(tree):
    """`tree` with every `Pair` as its parameter, joined to its accumulator;
    a tree that holds none comes back as it is."""
    return jax.tree.map(
        lambda x: summed(*x) if _is_pair(x) else x, tree, is_leaf=_is_pair)


def scan(body, init, xs):
    """`jax.lax.scan(body, init, xs)`, where `body` sees each `Pair` among
    `xs` as its parameter's slice, joined to that row of its accumulator
    (module docstring). With no `Pair` it is that call."""
    leaves, treedef = jax.tree.flatten(xs, is_leaf=_is_pair)
    if not any(map(_is_pair, leaves)):
        return jax.lax.scan(body, init, xs)
    at = [j for j, x in enumerate(leaves) if _is_pair(x)]
    accs = [leaves[j].acc for j in at]
    plain = [x.w if _is_pair(x) else x for x in leaves]

    def step(carry, scanned):
        (inner, accs), (row, slices) = carry, scanned
        accs = list(accs)
        for k, j in enumerate(at):
            slices[j], accs[k] = attach(slices[j], accs[k], row)
        inner, ys = body(inner, treedef.unflatten(slices))
        return (inner, accs), ys

    (out, accs), ys = jax.lax.scan(
        step, (init, accs), (jnp.arange(plain[0].shape[0]), plain))
    return seeded(out, accs), ys

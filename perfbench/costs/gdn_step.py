"""Gated DeltaNet's decode update over the pool's states, one decay per head
(``S' = a S; u = S'^T k; S = S' + b k (v - u)^T; o = S^T q``): what one call
cannot do without, from the call's own operand shapes, whatever their layout.

The state is the largest float32 operand, ``[..., dk, lanes]`` (heads side
by side along the lanes). Bytes: the state read once and written once, every
other operand (``q``, ``k`` and ``b k`` as columns, the decay spread over the
lanes, ``v``) read once, and ``o``, one value a lane of each state, written
once; all float32. FLOPs: per state element one multiply for the decay and a
multiply-add each for ``u``, the rank-one update and the read-out, 7 in all.
Every slot's state is streamed, live or idle, as in ``kda_step``."""


def _size(dims):
    n = 1
    for d in dims:
        n *= d
    return n


def cost(result, operands, cfg):
    floats = [o[1] for o in operands if o[0] == "f32"]
    state = max(floats, key=_size)
    elements = _size(state)
    rest = sum(_size(o) for o in floats) - elements
    return 7 * elements, 4 * (2 * elements + rest + elements // state[-2])

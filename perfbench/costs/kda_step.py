"""The delta rule's decode update over ``N`` (row, head) states of ``[dk,
dv]`` float32 (``S' = Diag(a) S; u = S'^T k; S = S' + b k (v - u)^T; o = S^T
q``): what one call cannot do without, from the call's own operand shapes.

Bytes: the state read once and written once, the four ``dk``-vectors (``q``,
``k``, ``b k``, ``a``) and ``v`` read once, ``o`` written once, all float32.
FLOPs: per state element one multiply for the decay and a multiply-add each
for ``u``, the rank-one update and the read-out, 7 in all. The state is the
largest operand; every slot's state is streamed, live or idle, which is the
algorithm's own cost on a pool (``row_state_kb_per_token.req`` says what the
idle ones cost)."""


def cost(result, operands, cfg):
    def size(dims):
        n = 1
        for d in dims:
            n *= d
        return n

    n, dk, dv = max((o[1] for o in operands if o[0] == "f32"), key=size)
    return 7 * n * dk * dv, 4 * (2 * n * dk * dv + 4 * n * dk + 2 * n * dv)

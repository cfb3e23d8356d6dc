"""Operations and bytes of a flash-attention call whose queries and keys are
wider than its values (latent attention as it trains: a head's query and key
are ``nope + rope`` wide, of which the ``rope`` part of the KEY is one head
that every query head uses; the values are ``v`` wide): the count functions
of the ``mla_flash_*_roofline`` metrics. ``flops.py``'s choices hold: a
multiply-add is two operations; under a causal mask half the pairs count;
the backward pass is the four products the gradient needs (dV, dP at the
values' width; dQ, dK at the queries'), the recomputed scores are the
kernel's own choice and are not counted. Nothing here looks at the program
or at a trace.

Bytes are what the mathematics has to move, not what a kernel that is handed
the rotary key laid out to every head does move: the rotary key is read
once a call (and its gradient written once), not once a head.
"""


def _sizes(batch, heads, seq_len, nope, rope, v, causal, bytes_per_elem):
    pairs = batch * heads * seq_len * (seq_len / 2 if causal else seq_len)
    token = batch * seq_len * bytes_per_elem  # one number a token, in bytes
    q = heads * (nope + rope) * token
    k = (heads * nope + rope) * token  # the rotary key once
    values = heads * v * token  # v, the output, or either's gradient
    lse = batch * heads * seq_len * 4
    return pairs, q, k, values, lse


def flash_forward(batch, heads, seq_len, nope, rope, v, causal, bytes_per_elem=2):
    """(operations, bytes) of one forward call: scores over ``nope + rope``,
    weights times values over ``v``; reads q, k, v, writes the output and
    one float32 log-sum-exp a query."""
    pairs, q, k, values, lse = _sizes(
        batch, heads, seq_len, nope, rope, v, causal, bytes_per_elem
    )
    return 2 * pairs * (nope + rope + v), q + k + 2 * values + lse


def flash_backward(batch, heads, seq_len, nope, rope, v, causal, bytes_per_elem=2):
    """(operations, bytes) of one backward call: dV and dP over ``v``, dQ
    and dK over ``nope + rope``; reads q, k, v, the output, its gradient and
    the log-sum-exp, writes dq, dk, dv."""
    pairs, q, k, values, lse = _sizes(
        batch, heads, seq_len, nope, rope, v, causal, bytes_per_elem
    )
    ops = 2 * pairs * 2 * (nope + rope + v)
    return ops, 2 * q + 2 * k + 4 * values + lse

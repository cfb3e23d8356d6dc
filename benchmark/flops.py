"""Operations and bytes that the mathematics needs, from shapes alone.

Every function here takes sizes and returns counts; none looks at the
program, a compiled executable or a trace. The choices, so that every PR
computes the same number:

* A multiply-add is two operations.
* Training costs three forward passes of matrix products (forward, and the
  two products of the backward pass). The optimizer, LayerNorm, softmax,
  GELU, the embedding lookups and anything recomputed are not counted.
* The output head is counted at the share of positions whose logits the
  loss needs (``head_token_share`` in the configuration's ``shape`` group:
  1 for next-token prediction, the masking rate for masked-LM), because a
  program that scores only those positions does all the work the loss asks
  for. The tied table is counted once, as the head's product; the lookup
  is not a product.
* Attention's two products (scores, and weights times values) are counted
  over the keys a query may see: all of them, or half under a causal mask.
* A flash kernel's backward pass is counted as the four products the
  gradient needs (dV, dP, dQ, dK). The recomputation of the scores inside
  the kernel is the kernel's own choice and is not counted, so the share
  of the roofline it can reach is at most 4/5 where it recomputes once.

A stack whose layers differ says so in its ``shape`` group, under
``layer_kinds``: a list of ``{"name", "count", "matmul_params",
"attention"}``. ``matmul_params`` is the weights of ONE such layer that one
token meets in matrix products, written out in the configuration's file
where a reader can check it against the widths; ``attention`` says whether
the layer has the two attention products. Without the key the stack is
``layers`` blocks of four d x d projections and a two-matrix MLP, each with
attention. What a kind counts:

* Grouped queries: the q and o projections at the query heads' width, k and
  v at the key heads'. The two attention products are at the query heads'
  width (``heads * head_dim``) whatever the grouping.
* A gated MLP has three matrices.
* A gated convolution: its projections in and out are products and are
  counted; the depthwise taps along the sequence (a few multiply-adds a
  channel) are not, like every other elementwise pass.
* Experts: what was routed to experts held here. Per token that is the
  experts per token, times one expert's matrices, times the share of the
  layer's experts that this chip holds, plus the router, which every token
  meets whole. The count is static, so it assumes even routing; a cell says
  beside it, by a counter of the program, what the routing really was.
"""


def matmul_params(shape):
    """Weights that take part in a matrix product, per token, with the head
    weighted by the share of positions it is needed for."""
    d = shape["d_model"]
    if "layer_kinds" in shape:
        body = sum(k["count"] * k["matmul_params"] for k in shape["layer_kinds"])
    else:
        body = shape["layers"] * (4 * d * d + 2 * d * shape["d_ff"])
    head = shape["vocab"] * d + shape.get("head_extra_matmul_params", 0)
    return body + shape["head_token_share"] * head


def attention_flops_per_token(shape, seq_len):
    """Forward operations of the two attention products, per token."""
    visible = seq_len / 2 if shape["causal"] else seq_len
    width = shape["heads"] * shape["head_dim"]
    if "layer_kinds" in shape:
        layers = sum(k["count"] for k in shape["layer_kinds"] if k["attention"])
    else:
        layers = shape["layers"]
    return layers * 2 * 2 * visible * width


def train_flops_per_token(shape, seq_len):
    """Model operations of one training step, per token of the batch."""
    forward = 2 * matmul_params(shape) + attention_flops_per_token(shape, seq_len)
    return 3 * forward


def flash_forward(batch, heads, seq_len, head_dim, causal, bytes_per_elem=2):
    """(operations, bytes) of one forward call of an attention kernel that
    keeps no S x S matrix: reads q, k, v, writes the output and one float32
    log-sum-exp per query."""
    pairs = batch * heads * seq_len * (seq_len / 2 if causal else seq_len)
    tensor = batch * heads * seq_len * head_dim * bytes_per_elem
    return 2 * 2 * pairs * head_dim, 4 * tensor + batch * heads * seq_len * 4


def flash_backward(batch, heads, seq_len, head_dim, causal, bytes_per_elem=2):
    """(operations, bytes) of one backward call: four products; reads q, k,
    v, the output, its gradient and the log-sum-exp, writes dq, dk, dv."""
    pairs = batch * heads * seq_len * (seq_len / 2 if causal else seq_len)
    tensor = batch * heads * seq_len * head_dim * bytes_per_elem
    return 4 * 2 * pairs * head_dim, 8 * tensor + batch * heads * seq_len * 4


def roofline_seconds(flops, nbytes, peak):
    """(least seconds the chip could take, which bound applies)."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")

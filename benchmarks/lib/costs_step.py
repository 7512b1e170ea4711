"""Operations the ALGORITHM needs for the whole model step of a served
token, from the configuration's own leaves: the yardstick of
`step_mfu.serve`, the whole step's share of the chip's peak that stands
beside the kernels' shares of their rooflines (a kernel taken off the
path leaves its roofline silent; the step's share still bounds a claim).

Counted: 2 operations a weight of every matrix a token passes through,
read off the reference's `param_shapes` (the program's own leaf paths)
so that a new family needs no code here:

- a leaf named `.../kernel` is a matrix every token passes through once
  (a scanned stack `[layers, in, out]` once a layer: every element
  counts);
- a leaf named `experts_*` is a table `[experts held, in, out]` of which
  a token passes through `num_experts_per_tok` of the router's outputs
  (`router_width`, else the experts there are): the share of its picks
  that land on an expert held here, one matrix each;
- the head (`lm_head/kernel`) is needed for the ONE row a prompt's
  first token is read from and for every output token, not for every
  prompt row; the embedding is a lookup.

Left out, so the share is a floor and cannot pass 100: the products over
the context (scores, values, the indexers' and selectors' scores, the
recurrences' states), which follow the context length and have shares of
roofline of their own; norms, gates and convolutions' taps (under a
thousandth of the weights). Recomputation and padding are not counted.
"""

from __future__ import annotations

HEAD = "lm_head/kernel"
EXPERTS = "experts_"


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def weight_flops_per_token(shapes: dict, cfg: dict) -> tuple:
    """(operations a token of the body, operations a row of the head)
    from `{leaf path: (shape, dtype)}` and the configuration."""
    body = head = 0.0
    for path, (shape, _) in shapes.items():
        leaf = path.rsplit("/", 1)[-1]
        if path == HEAD:
            head += 2.0 * _size(shape)
        elif leaf == "kernel":
            body += 2.0 * _size(shape)
        elif leaf.startswith(EXPERTS):
            routed = cfg.get("router_width") or cfg.get(
                "n_routed_experts") or cfg["num_experts"]
            body += 2.0 * _size(shape) * cfg["num_experts_per_tok"] / routed
    return body, head

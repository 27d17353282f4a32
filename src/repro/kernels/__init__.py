"""Pallas TPU kernels for the perf-critical compute layers.

  bst_search       -- the paper's search pipeline: forest-batched descent
                      over one flat level-major tree operand (DESIGN.md §2);
                      the hybrid configuration runs route + queue/direct
                      dispatch + stall-round replay in the same body (§8)
  queue_dispatch   -- the paper's queue-mapped buffers as a standalone
                      kernel (prefix-sum compaction; the BST hybrid path
                      now dispatches inside the forest kernel itself)
  flash_attention  -- LM substrate hot-spot (32k prefill cells)

Each has a pure-jnp oracle in ref.py and a jitted wrapper in ops.py.
The BST wrappers compile their kernel with Mosaic on a TPU and run it in
the Pallas interpreter on any other backend (``ops.interpret_mode``).
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]

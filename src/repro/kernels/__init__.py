"""Pallas TPU kernels: OMD routing update, flow step and the fused control
megakernel — dense and sparse (segment/edge-list) variants.

Each kernel has a jnp oracle in ref.py and a padded jit wrapper in ops.py;
validated in interpret mode (tests/test_kernels.py)."""
from . import ref
from .ops import (flow_in_edges_sparse, flow_step_op, flow_step_sparse_op,
                  omd_update_op, omd_update_sparse_op)

__all__ = ["ref", "flow_in_edges_sparse",
           "flow_step_op", "flow_step_sparse_op", "omd_update_op",
           "omd_update_sparse_op"]

"""Grouped matrix multiplication: rows sorted by group, each group times its
own weight matrix (the held experts of an MoE layer)."""
from repro.kernels.grouped_matmul.ops import grouped_matmul

__all__ = ["grouped_matmul"]

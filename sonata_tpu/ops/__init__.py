"""Kernels of this repo (Pallas), each in a module with its fallback off
a TPU: ``grouped_matmul`` (the expert products of a unit voice) and
``slot_attention`` (the slots' keys and values, and a step's attention over
them)."""

"""Pallas TPU kernels for the framework's compute hot spots (the paper
itself is an I/O paper — see DESIGN.md §2): flash attention
(flash_attention.py), with its pure-jnp oracle in ref.py, and the Mamba2
SSD scan (ssd_scan.py), whose oracles are repro.models.ssm's ssd_chunked
and reference_scan."""
from repro.kernels import ref

__all__ = ["ref"]

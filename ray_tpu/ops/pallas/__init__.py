"""Pallas TPU kernels for the hot ops, a file a mechanism, each a
forward and a backward kernel under one ``custom_vjp`` with the path
chosen from what the caller observes (the backend, the shapes, the
mesh), never from an environment variable:

- ``flash_attention``: causal flash attention, windows and unequal
  widths (latent attention) among its grids;
- ``ce_lse``: the LM head's product with the softmax's reduction as its
  epilogue;
- ``ssd_scan``, ``mamba1_scan``, ``kda_scan``: the Mamba-2 chunked scan,
  the Mamba-1 selective scan, the chunked gated delta rule (Kimi Delta
  Attention's, Gated DeltaNet's);
- ``causal_conv``, ``gated_norm``: the depthwise causal convolution with
  its SiLU, the gated RMSNorms behind a recurrence;
- ``cca_mix``, ``hc_maps``: CCA's passes in front of its flash kernel,
  mHC's residual maps;
- ``route_rows``: the routed layer's row moves without a scatter;
- ``router_choice``: a router's scores, ``top_k``, chosen weights,
  counts and sums in one pass over the product, its backward a one-hot
  pass (``ops/moe.py::_route`` / ``_route_sigmoid``).

``program`` is not a kernel: it answers once, for all of them, which
program a bare ``pallas_call`` may run in. The dispatchers are a level
up (``ops/*.py``), and nothing here imports from that level but the
names a recomputed block keeps (``ops/remat.py``).
"""

from ray_tpu.ops.pallas.flash_attention import (
    flash_attention,
    flash_attention_available,
)

__all__ = ["flash_attention", "flash_attention_available"]

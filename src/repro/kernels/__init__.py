"""Pallas TPU kernels for the framework's compute hot-spots.

* ``flash_attention`` — blockwise online-softmax attention (causal / sliding
  window / softcap / GQA), VMEM-tiled via BlockSpec, and its
  FlashAttention-2 backward; ``ops.attention`` is differentiable.
* ``adaseg_update``  — fused LocalAdaSEG extragradient update kernels
  (explore/anchor/one-shot): η-from-Σ(Z_τ)² computed in-register, box clip
  or two-pass l2-ball projection, and the (Z_t)²/‖G‖² reductions fused into
  the update passes. This is the production step path — selected by
  ``core.adaseg.local_step(backend="fused")``.
* ``sync_compress``  — fused Parameter-Server sync codecs: the Line-5/7
  uplink (error-feedback add + 1/η weighting + stochastic quantize with
  in-kernel threefry bits / top-k masking + residual write-back) and the
  server-side weighted merge, one HBM sweep each where the reference path
  takes ~5 tree passes. Selected by ``codec_backend="fused"`` on
  ``repro.ps`` engine configs.
* ``ssd_scan``       — Mamba2 SSD chunked scan (intra-chunk MXU matmuls +
  inter-chunk recurrence over summary states).

Each kernel ships ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd wrapper; interpret mode on the CPU only) and ``ref.py`` (pure-jnp
oracle). ``tiling.py`` holds the (8, 128) slab layout and VMEM block
sizing the elementwise kernels share.
"""

"""Pallas TPU kernels for the perf-critical compute layers, each shipped as:

  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — public wrapper with backend dispatch
              ('xla' = pure-jnp lowering used on the CPU dry-run,
               'pallas' = TPU kernel, 'interpret' = kernel body executed in
               Python for CPU validation); ssd's takes no backend: it
               chooses its path from the platform and the mesh
  ref.py    — pure-jnp oracle the tests sweep shapes/dtypes against

Kernels: flash_attention (train/prefill), decode_attention (KV-cache decode),
ssd (Mamba-2 state-space-dual chunk scan), rmsnorm (fused residual+norm).
"""

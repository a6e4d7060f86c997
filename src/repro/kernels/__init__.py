"""Pallas TPU kernels for the perf-critical compute (DESIGN.md §2):

* spmm            -- block-ELL sparse feature propagation with NAP row-block
                    predication (the paper's hot loop)
* nap_exit        -- fused distance-to-stationary + exit decision (Eq. 8 +
                    Algorithm 1 line 11)
* flash_attention -- tiled attention with sliding-window banding (local
                    layers + the long-context serving variant)

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper), ref.py (pure-jnp oracle). Interpret mode follows the platform
(`repro.runtime.pallas_interpret`): emulated on the CPU, where the tests
validate against the oracles, compiled on the TPU.
"""

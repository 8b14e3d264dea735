"""fpga_ai_nic_tpu — a TPU-native reimagination of libxsmm/fpga_ai_nic.

The reference (an Intel Arria-10 FPGA "AI smart NIC") offloads the gradient
all-reduce *and* the SGD weight update of data-parallel training onto the NIC,
optionally compressing ring traffic with block-floating-point (BFP).  This
package rebuilds every capability of that system TPU-first:

- ``ops.bfp``          — BFP codec (ref: hw/bf16_to_bfp_core.sv, hw/bfp_to_bf16_core.sv)
- ``compress``         — pluggable gradient-compression codec subsystem: the
                         Codec protocol + registry with bfp / top-k (error
                         feedback, SparCML-style) / int8 (stochastic rounding,
                         EQuARX-style) — the generalization of the single
                         wire trick the reference hard-wires (docs/COMPRESSION.md)
- ``ops.ring``         — sliced ring reduce-scatter / all-gather over ``lax.ppermute``
                         (ref: hw/all_reduce.sv st_eth_t FSM)
- ``ops.fused_update`` — fused scatter → SGD → all-gather-of-updated-weights
                         (ref: hw/weight_update.sv + hw/all_reduce.sv)
- ``parallel``         — mesh / sharding / DP / ZeRO-1 / TP / SP train steps
                         (ref: sw/mlp_mpi_example_f32.cpp training driver)
- ``runtime``          — async collective queue with bounded in-flight window and
                         done-flag futures (ref: sw/mlp_mpi_example_f32.cpp:114-180),
                         native C++ host codec (csrc/)
- ``models``           — MLP / ResNet-50 / BERT / Llama model zoo (BASELINE.json configs)
- ``utils``            — unified config system, observability, checkpointing

Nothing here is a translation: the compute path is JAX/XLA/Pallas over a
``jax.sharding.Mesh``; collectives ride ICI via ``psum_scatter``/``ppermute``.
"""

__version__ = "0.1.0"

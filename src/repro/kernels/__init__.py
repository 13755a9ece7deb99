# Pallas TPU kernels (validated with interpret=True on CPU; the XLA twins
# in repro.models.* are what the CPU dry-run lowers):
#   staging_pack    — egress block pack + fused int8 quantize (paper's block
#                     knob as a BlockSpec tile; §6 data reduction)
#   flash_attention — online-softmax prefill kernel, GQA via index_map,
#                     causal block skip, window + softcap
#   ssm_scan        — mamba1 selective scan, VMEM-resident state
#   decode_matmul   — few-row matmul that reads a float32 weight (one layer
#                     of a stack) once and casts its tiles in VMEM; the
#                     decode path runs it (interpret mode off the TPU)

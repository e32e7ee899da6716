"""Hand-written Pallas TPU kernels for the hot ops.

The reference ships hand-written CUDA kernels where cuBLAS/cuDNN fall
short (paddle/legacy/cuda/src/hl_*.cu, operators/math/*.cu); the TPU
analog is Pallas: VMEM-blocked kernels feeding the MXU, used where XLA's
automatic fusion can't deliver (flash attention's online softmax; the
chunked state-space scan, whose [chunk, chunk] matrices stay in VMEM).
Kernels run compiled on TPU and in interpreter mode on CPU (tests).
"""

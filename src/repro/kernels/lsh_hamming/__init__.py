from repro.kernels.lsh_hamming.ops import hamming_topk, hamming_topk_t
from repro.kernels.lsh_hamming import ref

__all__ = ["hamming_topk", "hamming_topk_t", "ref"]

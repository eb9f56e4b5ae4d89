"""Core library: the paper's parallel construction algorithms in JAX.

Public API for wavelet trees/matrices and rank/select structures
(Shun 2016, "Improved Parallel Construction of Wavelet Trees and
Rank/Select Structures").
"""
from . import bitops, scan, sort
from .huffman import (HuffmanWaveletTree, build_huffman_wavelet_tree,
                      canonical_codes, huffman_code_lengths, huffman_codebook,
                      reference_huffman_levels)
from .multiary import (MultiaryWaveletTree, build_multiary_wavelet_tree,
                       mwt_access, mwt_rank, mwt_select)
from .rank_select import (BinaryRank, BinarySelect, BitVector,
                          GeneralizedRankSelect, access_bit,
                          build_binary_rank, build_binary_select,
                          build_bitvector, build_generalized,
                          generalized_access, generalized_rank,
                          generalized_select, rank0, rank1, select0, select1)
from .sort import (bucket_ranks, counting_rank, radix_sort_stable,
                   sort_pass, sort_permutation)
from .wavelet_matrix import (WaveletMatrix, build_wavelet_matrix,
                             build_wavelet_matrix_levelwise, num_levels,
                             reverse_bits, wm_access, wm_access_descend,
                             wm_block_starts, wm_child_interval, wm_descend,
                             wm_interval_zeros, wm_position_step, wm_rank,
                             wm_select)
from .wavelet_tree import (WaveletTree, build_wavelet_tree,
                           build_wavelet_tree_dd,
                           build_wavelet_tree_levelwise, wt_access, wt_rank,
                           wt_select)

__all__ = [
    "bitops", "scan", "sort",
    "BinaryRank", "BinarySelect", "BitVector", "GeneralizedRankSelect",
    "access_bit", "build_binary_rank", "build_binary_select",
    "build_bitvector", "build_generalized", "generalized_access",
    "generalized_rank", "generalized_select", "rank0", "rank1",
    "select0", "select1",
    "bucket_ranks", "counting_rank", "radix_sort_stable", "sort_pass",
    "sort_permutation",
    "WaveletMatrix", "build_wavelet_matrix", "build_wavelet_matrix_levelwise",
    "num_levels", "reverse_bits", "wm_access", "wm_access_descend",
    "wm_block_starts", "wm_child_interval", "wm_descend",
    "wm_interval_zeros", "wm_position_step", "wm_rank", "wm_select",
    "WaveletTree", "build_wavelet_tree", "build_wavelet_tree_dd",
    "build_wavelet_tree_levelwise", "wt_access", "wt_rank", "wt_select",
    "HuffmanWaveletTree", "build_huffman_wavelet_tree", "canonical_codes",
    "huffman_code_lengths", "huffman_codebook", "reference_huffman_levels",
    "MultiaryWaveletTree", "build_multiary_wavelet_tree", "mwt_access",
    "mwt_rank", "mwt_select",
]

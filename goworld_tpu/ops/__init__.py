"""TPU compute ops: the AOI visibility pass and its parity oracle."""

from .aoi_predicate import (  # noqa: F401
    LANE,
    WORD_BITS,
    interest_matrix,
    pack_rows,
    pairs_from_matrix,
    pairs_from_words,
    round_capacity,
    unpack_rows,
    words_per_row,
)
from .aoi_oracle import CPUAOIOracle  # noqa: F401
from .aoi_dense import aoi_step_dense, aoi_step_dense_batched  # noqa: F401
from .aoi_stage import apply_packet, delta_scatter, delta_scatter_1d, \
    pad_packet  # noqa: F401
from .events import extract_pairs, popcount_total, unpack_words  # noqa: F401

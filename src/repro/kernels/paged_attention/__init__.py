from repro.kernels.paged_attention.merge import (  # noqa: F401
    merge_partials,
    resolve_partitions,
)
from repro.kernels.paged_attention.ops import (  # noqa: F401
    decode_walk_pages,
    paged_attention_partial,
    paged_chunk_attention,
)
from repro.kernels.paged_attention.ref import (  # noqa: F401
    gather_table_pages,
    paged_attention_partial_ref,
    paged_chunk_attention_ref,
    paged_to_dense,
)

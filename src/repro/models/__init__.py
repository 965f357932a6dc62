"""Model zoo: shared layers + heterogeneous-stack assembly."""

from repro.models.transformer import (  # noqa: F401
    copy_paged_cache_page,
    decode_step,
    encode,
    extract_cache_pages,
    forward,
    init_caches,
    init_paged_caches,
    insert_cache_pages,
    merge_slot_paged_caches,
    model_init,
    prefill,
    scatter_prefill_paged_caches,
)

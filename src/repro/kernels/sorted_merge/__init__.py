from .ops import (  # noqa: F401
    merge_compact,
    merge_compact_sharded,
    merge_edits_xla,
    require_pallas_fits,
)

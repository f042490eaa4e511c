from .ops import (  # noqa: F401
    connected_components,
    label_step,
    label_step_xla,
    merge_labels,
    require_pallas_fits,
)

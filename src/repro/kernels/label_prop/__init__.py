from .ops import (  # noqa: F401
    connected_components,
    label_step,
    label_step_xla,
    require_pallas_fits,
    spanning_forest,
)

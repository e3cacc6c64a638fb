from .mesh import (
    HW,
    hw_peaks,
    make_local_mesh,
    make_mesh,
    make_production_mesh,
)

__all__ = [
    "HW", "hw_peaks", "make_local_mesh", "make_mesh", "make_production_mesh",
]

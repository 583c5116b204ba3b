from repro.data.phantoms import (random_ellipse_phantom, shepp_logan_2d,
                                 analytic_parallel_projection)
from repro.data.pipeline import CTDataPipeline

__all__ = ["random_ellipse_phantom", "shepp_logan_2d",
           "analytic_parallel_projection", "CTDataPipeline"]

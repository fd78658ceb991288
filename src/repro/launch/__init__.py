# Launch layer: device meshes, compiled dry-runs, and roofline/HLO
# analysis of the lowered cells. `dryrun` and `report` stay script-style
# entry points (python -m repro.launch.dryrun / .report).
from .hlo_analysis import CollectiveStats, analyze_collectives
from .mesh import make_host_mesh, make_mesh, make_production_mesh
from .roofline import (HBM_BW, ICI_BW, PEAK_FLOPS, RooflineTerms,
                       count_params, model_flops, terms_from_analysis)

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh",
           "CollectiveStats", "analyze_collectives",
           "RooflineTerms", "terms_from_analysis", "count_params",
           "model_flops", "PEAK_FLOPS", "HBM_BW", "ICI_BW"]

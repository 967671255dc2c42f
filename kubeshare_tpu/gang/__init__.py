"""Gang isolation plane: gang-atomic token grants over carved ICI
sub-meshes (doc/gang.md).

:mod:`.coordinator` — :class:`~.coordinator.GangTokenCoordinator`,
two-phase reserve/commit grants spanning every member chip.
:mod:`.carve` — the ``KUBESHARE_TPU_VISIBLE_CHIPS`` carve format
(``chip@x.y``) and block validation against the planned sub-mesh.
"""

from .carve import (CarveError, block_coords, carve_block, carve_env,
                    format_mesh, parse_mesh, parse_visible_chips, strip_carve)
from .coordinator import GangTokenCoordinator

__all__ = [
    "CarveError", "GangTokenCoordinator", "block_coords", "carve_block",
    "carve_env", "format_mesh", "parse_mesh", "parse_visible_chips",
    "strip_carve",
]

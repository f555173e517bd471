from repro.launch.mesh import (CHIP_PEAKS, chip_peaks,
                               make_debug_mesh, make_production_mesh)

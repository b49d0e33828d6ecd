from repro.launch.mesh import (data_axes, make_mesh, make_production_mesh,
                               make_test_mesh, split_duet_submeshes)

__all__ = ["data_axes", "make_mesh", "make_production_mesh",
           "make_test_mesh", "split_duet_submeshes"]

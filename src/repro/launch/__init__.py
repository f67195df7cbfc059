from .mesh import campaign_mesh, make_host_mesh

"""Distribution: logical-axis sharding rules, mesh helpers, context."""
from .ctx import constrain, axis_size, mesh_context  # noqa: F401

"""prefix_hit_share: see ``readers.prefix_hit_share``."""
from readers import prefix_hit_share as read  # noqa: F401

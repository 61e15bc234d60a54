"""decode_occupancy: see ``readers.decode_occupancy``."""
from readers import decode_occupancy as read  # noqa: F401

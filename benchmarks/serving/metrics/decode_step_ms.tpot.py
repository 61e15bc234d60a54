"""decode_step_ms.tpot: see ``readers.decode_step_ms``."""
from readers import decode_step_ms as read  # noqa: F401

"""tpot_p90: see ``readers.tpot_p90``."""
from readers import tpot_p90 as read  # noqa: F401

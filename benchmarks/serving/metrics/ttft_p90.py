"""ttft_p90: see ``readers.ttft_p90``."""
from readers import ttft_p90 as read  # noqa: F401

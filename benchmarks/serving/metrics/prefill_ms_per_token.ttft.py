"""prefill_ms_per_token.ttft: see ``readers.prefill_ms_per_token``."""
from readers import prefill_ms_per_token as read  # noqa: F401

"""prefill_ms_per_token.tput: see ``readers.prefill_ms_per_token``."""
from readers import prefill_ms_per_token as read  # noqa: F401

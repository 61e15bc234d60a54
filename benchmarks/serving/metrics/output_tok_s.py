"""output_tok_s: see ``readers.output_tok_s``."""
from readers import output_tok_s as read  # noqa: F401

"""``ring.upload_bytes_per_event`` as the clients cell reads it, where it moves
``lookups_per_s.clients``."""
from chipbench.common import load_reader

read = load_reader("ring.upload_bytes_per_event")

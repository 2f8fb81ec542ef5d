"""``ring.idle_share`` as the clients cell reads it, where it moves
``lookups_per_s.clients``."""
from chipbench.common import load_reader

read = load_reader("ring.idle_share")

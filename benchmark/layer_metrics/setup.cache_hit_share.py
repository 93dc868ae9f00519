"""Of the program builds before the window whose executable the persistent
compilation cache holds or would hold, the share that hit an entry it held
when the process started (``setup_log.cache_hit_share``): 1.0 a warm line, 0
a cold one. The number every ``setup_s`` is read beside."""
import setup_log


def read(ctx):
    return setup_log.cache_hit_share(ctx)

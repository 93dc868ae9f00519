"""What the slide leaves of the window layers' cache: blocks the sliding pool
holds over the blocks one table would hold for the same sequences
(``win_blocks_busy`` over ``full_blocks_busy`` of the matched ``engine/dispatch``
spans), per cent. A property of the traffic's lengths and the window, not of
speed. ``swa_spans.window_held_share``."""
from swa_spans import window_held_share as read  # noqa: F401

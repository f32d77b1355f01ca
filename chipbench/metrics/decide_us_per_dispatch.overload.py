"""``decide_us_per_dispatch`` where the cell is judged on
``completed_fps``: past the knee many frames are ready at each turn, and
the MapScore loop over them holds the engine loop away from the device."""
from chipbench.run import reader

read = reader("decide_us_per_dispatch")

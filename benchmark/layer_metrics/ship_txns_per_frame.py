"""Inter-DC sender (interdc/sender.py): transactions shipped per batch
frame over the window, counters ``ship_txns`` / ``ship_frames`` of kind
``batch``.  More per frame is fewer frames to send, receive and apply a
transaction; a frame also closes after ``interdc_ship_us``, so the
value follows the commit rate as well.  Moves ``vis_lag_p95_ms``."""


def read(w):
    frames = w.counters.get("ship_frames", 0)
    return w.counters["ship_txns"] / frames if frames else None

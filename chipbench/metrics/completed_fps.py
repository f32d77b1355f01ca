"""Frames whose result was seen ready in the window, per second, in time
or late: the frames the engine delivered. Past the knee this is the
steady measure of the engine's work; which of them met their deadline
(``goodput_fps``) swings with small changes of timing."""


def read(run):
    done = sum(1 for f in run.frames
               if f.ready is not None and f.ready < run.queue_until)
    return done / run.queue_until

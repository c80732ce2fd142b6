"""SIGTERM -> migration image durable (where the job would exit 85), mean
over the window's cycles. Starts at the signal, so it holds the rest of
the step the job was in."""
import bench


def read(rec):
    return bench.mean_of(rec, "preempt_durable")

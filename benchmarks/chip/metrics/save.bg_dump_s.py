"""Wall seconds of the background ckpt.dump each async save call submits
(plan, chunk work and manifest commit on the ordered lane), mean per
save."""
from program_spans import started_by


def read(rec):
    return started_by(rec, "save_call", "ckpt.dump")

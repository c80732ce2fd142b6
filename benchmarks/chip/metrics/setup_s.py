"""Process start -> window opens: backend init, corpus, state made on the
device from the seed, the cell's programs from the compile cache, the
job's first steps."""


def read(rec):
    return rec["setup_s"]

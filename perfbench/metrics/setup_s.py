"""setup_s: seconds from process start to the window: loading, the
native core, reaching the chip, crafting inputs, warm-up and compiles."""


def read(run):
    return run.setup_s

"""setup_s: seconds from process start to the window's open: data
synthesis, index build, staging, warm-up and compilation."""


def read(run):
    return run.setup_s

"""Images answered inside the window, over the window's length."""


def read(rec):
    return rec["images_done"] / rec["window_s"]

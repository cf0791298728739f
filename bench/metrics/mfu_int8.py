"""The whole served step's share (%) of the chip's int8 peak: the useful int8
operations per image (from the configuration's widths, unpadded;
bench/work.py) times the images answered per second in the window, over
chips x the int8 peak (bench/peaks.json).  Nothing when no image was
answered."""


def read(rec):
    if "peaks" not in rec or not rec["window_images_per_s"]:
        return None
    return 100.0 * rec["useful_ops_per_image"] * rec["window_images_per_s"] / (
        rec["chips"] * rec["peaks"]["int8_ops_per_s"])

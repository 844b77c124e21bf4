"""Images whose logits returned inside the window, per second of window.
Host clock."""


def read(run):
    if run.kind != "cnn":
        return None
    return run.images_in_window / run.window_s

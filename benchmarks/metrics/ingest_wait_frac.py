"""Share of the window the train loop spent blocked on its next staged
super-batch (the program's ``train.wait_input`` timer), in percent."""


def read(run):
    c = run["counters"]
    if "wait_input_s" not in c or not c.get("window_s"):
        return None
    return 100.0 * c["wait_input_s"] / c["window_s"]

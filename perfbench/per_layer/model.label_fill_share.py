"""Model: live labels over the label rows the tables reserve for them, at
the window's end: ``driver.num_labels`` over ``driver.label_capacity`` of
``get_status`` (the gauges ``model.labels_live`` and
``model.label_capacity`` on ``/metrics``). Capacity doubles from 8, so 20
labels read 62.5 and 2 read 25; the step sweeps and gathers every reserved
row. The fullest server's where there are several."""

NAME = "model.label_fill_share"


def read(run):
    shares = [100.0 * float(st["driver.num_labels"])
              / float(st["driver.label_capacity"]) for st in run.status1
              if st.get("driver.label_capacity")]
    return max(shares) if shares else None

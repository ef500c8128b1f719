"""The peak, over the window's once-a-second samples of the program's
gauges, of ``num`` over ``den``, in percent."""


def read(params, obs):
    ratios = [100.0 * g[params["num"]] / g[params["den"]]
              for g in obs.gauges if g.get(params["den"])]
    return max(ratios) if ratios else None

"""Persist: ``store.write`` spans (building each bin's forecasts and
``PredictionStore.save_many``) per window tick, in ms."""
import host_spans


def read(run):
    return host_spans.ms_per_tick(run, "store.write")

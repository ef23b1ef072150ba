"""Input ingestion plugins: anything -> device Table.

Mirrors the reference's input_utils package
(/root/reference/dask_sql/input_utils/): ``InputUtil.to_table`` probes
registered plugins in order (convert.py:66-79); plugins cover native tables,
pandas-likes, dict/record data, and file locations by extension
(location.py:10-34).  Hive/Intake/SQLAlchemy plugins exist as gated stubs —
their optional dependencies are not in this image.
"""
from __future__ import annotations

import os
from typing import Any, List, Optional

import numpy as np

from ..table import Table
from ..utils import Pluggable


class InputUtil(Pluggable):
    """Probes input plugins in registration order (reference convert.py:38-79)."""

    @classmethod
    def to_table(cls, input_item: Any, **kwargs) -> Table:
        if isinstance(input_item, list):
            from ..ops.join import concat_tables
            return concat_tables([cls.to_table(i, **kwargs) for i in input_item])
        for plugin in cls.get_plugins():
            if plugin.is_correct_input(input_item, **kwargs):
                return plugin.to_table(input_item, **kwargs)
        raise ValueError(f"Do not understand the input type {type(input_item)}")

    @classmethod
    def to_frame_or_table(cls, input_item: Any, **kwargs):
        """What ``Context.create_table`` loads from: the pandas frame of a
        plugin that reads one (so encoding, statistics and upload are the
        load's own three steps), else the plugin's device Table."""
        if not isinstance(input_item, list):
            for plugin in cls.get_plugins():
                if plugin.is_correct_input(input_item, **kwargs):
                    read = (plugin.to_frame
                            if isinstance(plugin, FrameInputPlugin)
                            else plugin.to_table)
                    return read(input_item, **kwargs)
        # a list of inputs, or the error for a type no plugin knows
        return cls.to_table(input_item, **kwargs)


class BaseInputPlugin:
    def is_correct_input(self, input_item, **kwargs) -> bool:
        raise NotImplementedError

    def to_table(self, input_item, **kwargs) -> Table:
        raise NotImplementedError


class FrameInputPlugin(BaseInputPlugin):
    """A plugin whose input becomes a pandas frame on its way to the device."""

    def to_frame(self, input_item, **kwargs):
        raise NotImplementedError

    def to_table(self, input_item, **kwargs) -> Table:
        return Table.from_pandas(self.to_frame(input_item, **kwargs))


class DeviceTableInputPlugin(BaseInputPlugin):
    """Already a device Table (analogue of DaskInputPlugin, dask.py:8)."""

    def is_correct_input(self, input_item, **kwargs):
        return isinstance(input_item, Table)

    def to_table(self, input_item, **kwargs):
        return input_item


class PandasLikeInputPlugin(FrameInputPlugin):
    """pandas DataFrame / Series (reference pandaslike.py:12)."""

    def is_correct_input(self, input_item, **kwargs):
        import pandas as pd
        return isinstance(input_item, (pd.DataFrame, pd.Series))

    def to_frame(self, input_item, **kwargs):
        import pandas as pd
        if isinstance(input_item, pd.Series):
            input_item = input_item.to_frame()
        return input_item


class DictInputPlugin(BaseInputPlugin):
    """dict of column -> values, numpy structured arrays."""

    def is_correct_input(self, input_item, **kwargs):
        return isinstance(input_item, dict)

    def to_table(self, input_item, **kwargs):
        return Table.from_pydict(input_item)


class ArrowInputPlugin(FrameInputPlugin):
    def is_correct_input(self, input_item, **kwargs):
        try:
            import pyarrow as pa
            return isinstance(input_item, pa.Table)
        except ImportError:
            return False

    def to_frame(self, input_item, **kwargs):
        return input_item.to_pandas()


class LocationInputPlugin(FrameInputPlugin):
    """File path -> reader by extension (reference location.py:10-34)."""

    def is_correct_input(self, input_item, **kwargs):
        return isinstance(input_item, str)

    def to_frame(self, input_item: str, file_format: Optional[str] = None,
                 **kwargs):
        import pandas as pd

        if not file_format:
            file_format = os.path.splitext(input_item)[1].lstrip(".")
        file_format = (file_format or "").lower()
        read_kwargs = {k: v for k, v in kwargs.items()
                       if k not in ("persist", "schema_name", "statistics",
                                    "gpu", "table_name")}
        if file_format in ("csv", "tsv", "txt"):
            if file_format == "tsv" and "sep" not in read_kwargs:
                read_kwargs["sep"] = "\t"
            df = pd.read_csv(input_item, **read_kwargs)
        elif file_format in ("parquet", "pq"):
            df = pd.read_parquet(input_item, **read_kwargs)
        elif file_format == "json":
            df = pd.read_json(input_item, **read_kwargs)
        elif file_format in ("feather", "arrow"):
            df = pd.read_feather(input_item, **read_kwargs)
        elif file_format == "orc":
            df = pd.read_orc(input_item, **read_kwargs)
        else:
            raise AttributeError(f"Do not understand input format {file_format}")
        return df


class HiveInputPlugin(BaseInputPlugin):
    """Hive metastore tables via any DB-API-ish cursor (io/hive.py holds the
    DESCRIBE FORMATTED machinery, reference hive.py:25-284)."""

    def is_correct_input(self, input_item, **kwargs):
        from .hive import HiveInput
        return HiveInput.is_hive_like(input_item, **kwargs)

    def to_table(self, input_item, **kwargs):
        from .hive import HiveInput
        return HiveInput.to_table(input_item, **kwargs)


class IntakeCatalogInputPlugin(BaseInputPlugin):
    """Intake catalogs (reference intake.py:14-34): the named catalog entry
    is read into pandas and encoded to a device Table.  Accepts a Catalog
    object or, with ``file_format="intake"``, a catalog path/URL."""

    @staticmethod
    def _intake():
        try:
            import intake
            return intake
        except ImportError:
            return None

    def is_correct_input(self, input_item, file_format=None, **kwargs):
        if file_format == "intake":
            # claimed even without intake installed, so to_table raises the
            # actionable ImportError instead of LocationInputPlugin's
            # "do not understand input format"
            return True
        intake = self._intake()
        return (intake is not None
                and isinstance(input_item, intake.catalog.Catalog))

    def to_table(self, input_item, table_name=None, file_format=None,
                 **kwargs):
        intake = self._intake()
        if intake is None:
            raise ImportError("Intake ingestion requires intake")
        table_name = kwargs.pop("intake_table_name", table_name)
        catalog_kwargs = kwargs.pop("catalog_kwargs", {})
        if isinstance(input_item, str):
            input_item = intake.open_catalog(input_item, **catalog_kwargs)
        # the reference materializes to dask (intake.py:34 `.to_dask()`);
        # here the source reads to pandas and uploads to the device
        read_kwargs = {k: v for k, v in kwargs.items()
                       if k not in ("persist", "schema_name", "statistics",
                                    "gpu")}
        source = input_item[table_name](**read_kwargs) if read_kwargs \
            else input_item[table_name]
        return Table.from_pandas(source.read())

"""Pydantic config base machinery.

TPU-native analog of the reference's ``deepspeed/runtime/config_utils.py``
(``DeepSpeedConfigModel`` :17): a pydantic BaseModel that supports deprecated
field migration (``deprecated=True, new_param=...`` in ``json_schema_extra``)
and the ``"auto"`` sentinel for autotunable values.
"""

from __future__ import annotations

from typing import Any, Dict

from pydantic import BaseModel, ConfigDict, model_validator

from deepspeed_tpu.utils.logging import logger, warning_once

AUTO_VALUE = "auto"


class DeepSpeedConfigModel(BaseModel):
    """Base for all config sections.

    Like the reference, unknown keys are tolerated (collected, warned about)
    rather than fatal, so configs written for the reference largely parse.
    """

    model_config = ConfigDict(
        extra="allow",
        populate_by_name=True,
        validate_assignment=True,
        arbitrary_types_allowed=True,
        protected_namespaces=(),
    )

    def __init__(self, _ds_strict: bool = False, **data):
        # _ds_strict is underscore-prefixed so it cannot collide with a config
        # key (unknown keys are tolerated and must pass through to the model).
        if not _ds_strict:  # drop "auto" values so field defaults apply
            data = {k: v for k, v in data.items() if v != AUTO_VALUE}
        super().__init__(**data)

    @model_validator(mode="before")
    @classmethod
    def _migrate_deprecated(cls, values: Any) -> Any:
        if not isinstance(values, dict):
            return values
        for name, field in cls.model_fields.items():
            extra = getattr(field, "json_schema_extra", None) or {}
            if not extra.get("deprecated", False):
                continue
            keys = {name}
            if field.alias:
                keys.add(field.alias)
            hit = next((k for k in keys if k in values), None)
            if hit is None:
                continue
            new_param = extra.get("new_param", "")
            logger.warning(f"Config parameter {hit} is deprecated" + (f"; use {new_param} instead" if new_param else ""))
            if new_param and new_param not in values:
                values[new_param] = values.pop(hit)
        return values

    @model_validator(mode="after")
    def _warn_unknown_keys(self):
        # once a process and key: pydantic runs this again at every assignment
        for key in self.extra_fields():
            warning_once(f"{type(self).__name__}: unknown config key {key!r} has no field "
                         "here and changes nothing")
        return self

    def extra_fields(self) -> Dict[str, Any]:
        return dict(self.__pydantic_extra__ or {})

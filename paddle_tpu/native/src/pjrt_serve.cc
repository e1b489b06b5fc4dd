// Python-free TPU serving via the PJRT C API.
//
// The reference's capi serves models with no interpreter in the process
// (reference: capi/gradient_machine.h:36-112). On TPU the compiled-
// execution engine IS the XLA runtime, so the Python-free path is the
// PJRT C ABI exported by the platform plugin (libtpu.so exports
// GetPjrtApi): dlopen the plugin, create a client, compile the raw
// StableHLO module exported by paddle_tpu.serve.artifact
// (program.mlir, format "mlir"), and execute — CPython never enters the
// process. This is SURVEY §7's prescribed "XLA AOT / PJRT-C" serving
// path; the CPU counterpart for plugin-less hosts is infer.cc.
//
// Scope: single-device inference, one f32 input -> one f32 output (the
// shape exported by serve.artifact for classification forwards). The
// compile options proto is hand-encoded (field numbers from
// xla/pjrt/proto/compile_options.proto: executable_build_options=3;
// within it device_ordinal=1, num_replicas=4, num_partitions=5) so the
// library needs no protobuf dependency.
//
// Thread contract mirrors infer.cc: one loaded handle may be driven by
// many threads; PJRT clients/executables are thread-safe.

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <memory>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

thread_local std::string g_error;

std::string error_message(const PJRT_Api* api, PJRT_Error* err) {
  if (!err) return "";
  PJRT_Error_Message_Args margs;
  memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
  return msg;
}

#define CHECK_PJRT(api, call)                         \
  do {                                                \
    PJRT_Error* _err = (call);                        \
    if (_err) {                                       \
      g_error = error_message(api, _err);             \
      return nullptr;                                 \
    }                                                 \
  } while (0)

#define CHECK_PJRT_RC(api, call)                      \
  do {                                                \
    PJRT_Error* _err = (call);                        \
    if (_err) {                                       \
      g_error = error_message(api, _err);             \
      return 1;                                       \
    }                                                 \
  } while (0)

// default CompileOptionsProto: executable_build_options {
//   device_ordinal: -1  num_replicas: 1  num_partitions: 1 }
// compile_portable_executable: true
// Portable matters: pts_forward passes execute_device, and PJRT routes
// that to ExecutePortable, which rejects executables that hold a
// compile-time device assignment.
std::string default_compile_options() {
  std::string inner;
  inner += '\x08';  // field 1 varint (device_ordinal)
  for (int i = 0; i < 9; i++) inner += '\xff';
  inner += '\x01';  // -1 as 10-byte two's-complement varint
  inner += '\x20';  // field 4 varint (num_replicas)
  inner += '\x01';
  inner += '\x28';  // field 5 varint (num_partitions)
  inner += '\x01';
  std::string outer;
  outer += '\x1a';  // field 3, length-delimited
  outer += static_cast<char>(inner.size());
  outer += inner;
  outer += '\x20';  // field 4 varint (compile_portable_executable)
  outer += '\x01';
  return outer;
}

struct Served {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  PJRT_Device* device = nullptr;

  // Destructor releases PJRT state so EVERY pts_load failure path (the
  // unique_ptr unwinding) frees the client — a chip belongs to one
  // client at a time, so a leaked one blocks all later
  // PJRT_Client_Create in this process.
  ~Served() {
    if (exec && api) {
      PJRT_LoadedExecutable_Destroy_Args args;
      memset(&args, 0, sizeof(args));
      args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      args.executable = exec;
      error_message(api, api->PJRT_LoadedExecutable_Destroy(&args));
    }
    if (client && api) {
      PJRT_Client_Destroy_Args args;
      memset(&args, 0, sizeof(args));
      args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
      args.client = client;
      error_message(api, api->PJRT_Client_Destroy(&args));
    }
    // leave the plugin dlopen'd: libtpu does not support re-dlopen
  }
};

// RAII for device buffers so pts_forward error paths can't leak HBM.
struct BufferGuard {
  const PJRT_Api* api;
  PJRT_Buffer* buf = nullptr;
  ~BufferGuard() {
    if (buf && api) {
      PJRT_Buffer_Destroy_Args args;
      memset(&args, 0, sizeof(args));
      args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      args.buffer = buf;
      error_message(api, api->PJRT_Buffer_Destroy(&args));
    }
  }
};

bool await_event(const PJRT_Api* api, PJRT_Event* ev) {
  PJRT_Event_Await_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  args.event = ev;
  PJRT_Error* err = api->PJRT_Event_Await(&args);
  PJRT_Event_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = ev;
  api->PJRT_Event_Destroy(&dargs);
  if (err) {
    g_error = error_message(api, err);
    return false;
  }
  return true;
}

std::string read_file(const char* path) {
  FILE* f = fopen(path, "rb");
  if (!f) return "";
  fseek(f, 0, SEEK_END);
  long n = ftell(f);
  fseek(f, 0, SEEK_SET);
  std::string out(n, '\0');
  size_t got = fread(out.data(), 1, n, f);
  fclose(f);
  out.resize(got);
  return out;
}

}  // namespace

extern "C" {

const char* pts_last_error() { return g_error.c_str(); }

// Load plugin + compile the StableHLO module at mlir_path.
void* pts_load(const char* plugin_so, const char* mlir_path) {
  auto s = std::make_unique<Served>();
  s->dl = dlopen(plugin_so, RTLD_NOW | RTLD_LOCAL);
  if (!s->dl) {
    g_error = std::string("dlopen failed: ") + dlerror();
    return nullptr;
  }
  auto get_api = reinterpret_cast<const PJRT_Api* (*)()>(
      dlsym(s->dl, "GetPjrtApi"));
  if (!get_api) {
    g_error = "plugin has no GetPjrtApi symbol";
    return nullptr;
  }
  s->api = get_api();
  const PJRT_Api* api = s->api;

  PJRT_Plugin_Initialize_Args init_args;
  memset(&init_args, 0, sizeof(init_args));
  init_args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  CHECK_PJRT(api, api->PJRT_Plugin_Initialize(&init_args));

  PJRT_Client_Create_Args cargs;
  memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  CHECK_PJRT(api, api->PJRT_Client_Create(&cargs));
  s->client = cargs.client;

  PJRT_Client_AddressableDevices_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = s->client;
  CHECK_PJRT(api, api->PJRT_Client_AddressableDevices(&dargs));
  if (dargs.num_addressable_devices == 0) {
    g_error = "no addressable devices";
    return nullptr;
  }
  s->device = dargs.addressable_devices[0];

  std::string code = read_file(mlir_path);
  if (code.empty()) {
    g_error = std::string("cannot read mlir module: ") + mlir_path;
    return nullptr;
  }
  std::string opts = default_compile_options();
  const char kFormat[] = "mlir";

  PJRT_Program program;
  memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = code.data();
  program.code_size = code.size();
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args comp;
  memset(&comp, 0, sizeof(comp));
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.client = s->client;
  comp.program = &program;
  comp.compile_options = opts.data();
  comp.compile_options_size = opts.size();
  CHECK_PJRT(api, api->PJRT_Client_Compile(&comp));
  s->exec = comp.executable;
  return s.release();
}

void pts_free(void* handle) {
  delete static_cast<Served*>(handle);  // ~Served releases PJRT state
}

// One f32 input [dims] -> one f32 output of out_elems floats.
int pts_forward(void* handle, const float* in, const int64_t* dims,
                int num_dims, float* out, int64_t out_elems) {
  auto* s = static_cast<Served*>(handle);
  const PJRT_Api* api = s->api;

  PJRT_Client_BufferFromHostBuffer_Args bargs;
  memset(&bargs, 0, sizeof(bargs));
  bargs.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  bargs.client = s->client;
  bargs.data = in;
  bargs.type = PJRT_Buffer_Type_F32;
  bargs.dims = dims;
  bargs.num_dims = num_dims;
  bargs.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  bargs.device = s->device;
  CHECK_PJRT_RC(api, api->PJRT_Client_BufferFromHostBuffer(&bargs));
  BufferGuard in_guard{api, bargs.buffer};
  if (!await_event(api, bargs.done_with_host_buffer)) return 1;
  PJRT_Buffer* in_buf = bargs.buffer;

  PJRT_ExecuteOptions eopts;
  memset(&eopts, 0, sizeof(eopts));
  eopts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_Buffer* const arg_list[] = {in_buf};
  PJRT_Buffer* const* arg_lists[] = {arg_list};
  PJRT_Buffer* out_list[1] = {nullptr};
  PJRT_Buffer** out_lists[] = {out_list};
  PJRT_Event* device_complete[1] = {nullptr};

  PJRT_LoadedExecutable_Execute_Args eargs;
  memset(&eargs, 0, sizeof(eargs));
  eargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  eargs.executable = s->exec;
  eargs.options = &eopts;
  eargs.argument_lists = arg_lists;
  eargs.num_devices = 1;
  eargs.num_args = 1;
  eargs.output_lists = out_lists;
  eargs.device_complete_events = device_complete;
  eargs.execute_device = s->device;
  CHECK_PJRT_RC(api, api->PJRT_LoadedExecutable_Execute(&eargs));
  BufferGuard out_guard{api, out_list[0]};
  if (device_complete[0] && !await_event(api, device_complete[0])) return 1;

  PJRT_Buffer_ToHostBuffer_Args hargs;
  memset(&hargs, 0, sizeof(hargs));
  hargs.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  hargs.src = out_list[0];
  hargs.dst = out;
  hargs.dst_size = out_elems * sizeof(float);
  CHECK_PJRT_RC(api, api->PJRT_Buffer_ToHostBuffer(&hargs));
  if (!await_event(api, hargs.event)) return 1;
  return 0;  // BufferGuards release both device buffers
}

}  // extern "C"
